"""The harness end to end on the CPU at a tiny size (interpret-mode
kernels): a sound run is correct; the float8 control and every fault a
serving cell can have come out not correct; no chip or no program means
exit 2 and no result line."""
import json

import jax.numpy as jnp
import pytest

import run
import tiny

SEED = ["--seed", "3000000017", "--seconds", "4"]


def _run(capsys, kind="open_loop", trace=0, control=0, record=None,
         **engine):
    extra = ["--record", str(record)] if record else []
    rc = run.main(["--workload", "x", *SEED, "--trace", str(trace),
                   "--control", str(control), *extra],
                  require_tpu=False, cell=tiny.cell(kind, **engine))
    out, err = capsys.readouterr()
    assert rc == 0, err
    return json.loads(out.strip().splitlines()[-1]), err


def test_open_loop_run_is_correct(capsys):
    res, err = _run(capsys, trace=1)
    assert res["correct"], err
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert {"far_miss_share", "outside_nucleus"} <= set(res["checks"])
    assert "jit traces in window 0" in err
    assert {"step_ms.online", "arrival_lag_p99_ms.online"} <= set(
        res["metrics"])


def test_backlog_run_is_correct(capsys):
    res, err = _run(capsys, kind="backlog")
    assert res["correct"], err
    assert {"output_tok_s", "setup_s"} <= set(res["metrics"])


def test_control_is_not_correct(capsys, tmp_path):
    """The reference in float8 (the precision below the configuration's
    bfloat16), its first tokens judged in place of the served ones by the
    same comparison, comes out not correct."""
    res, err = _run(capsys, control=1, record=tmp_path / "rec.json")
    rec = json.loads((tmp_path / "rec.json").read_text())
    assert res["correct"], err
    assert rec["control_correct"] is False, rec["control"]


@pytest.fixture
def altered_tokens(monkeypatch):
    """A served token altered where it is produced: the sampler's choice
    moved to the next id."""
    from repro.serve.engine import scheduler
    orig = scheduler._sample_tokens

    def wrong(*a, **k):
        return (orig(*a, **k) + 1) % tiny.CONFIG["model"]["vocab_size"]
    monkeypatch.setattr(scheduler, "_sample_tokens", wrong)


@pytest.fixture
def kv_not_written(monkeypatch):
    """A step that returns its state unchanged: new KV rows are never
    written to their pages."""
    from repro.models import layers
    monkeypatch.setattr(layers, "paged_write_rows",
                        lambda buf, val, bt, pos: buf)


@pytest.fixture
def altered_sampled_tokens(monkeypatch):
    """A sampled token altered where it is produced (greedy rows kept):
    the sampler's choice moved to the next id on rows with a temperature."""
    from repro.serve.engine import scheduler
    orig = scheduler._sample_tokens

    def wrong(keys, logits, temps, *a, **k):
        tok = orig(keys, logits, temps, *a, **k)
        moved = (tok + 1) % tiny.CONFIG["model"]["vocab_size"]
        return jnp.where(temps > 0, moved, tok)
    monkeypatch.setattr(scheduler, "_sample_tokens", wrong)


@pytest.mark.parametrize("fault,kind", [
    ("altered_tokens", "backlog"), ("kv_not_written", "backlog"),
    ("altered_sampled_tokens", "open_loop")])
def test_fault_is_not_correct(capsys, request, fault, kind):
    request.getfixturevalue(fault)
    res, err = _run(capsys, kind=kind)
    assert not res["correct"], err


def test_no_chip_exits_2(capsys):
    rc = run.main(["--workload", "granite-3-2b.batch", *SEED])
    out, err = capsys.readouterr()
    assert rc == 2 and out == "" and "needs a TPU" in err


def test_no_program_exits_2(capsys, tmp_path):
    rc = run.main(["--workload", "granite-3-2b.batch", *SEED], root=tmp_path)
    out, _ = capsys.readouterr()
    assert rc == 2 and out == ""
