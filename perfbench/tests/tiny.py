"""A tiny cell for rehearsals on the CPU (interpret-mode kernels): the
configuration and mix of a real cell, cut to a size a test run holds."""
import copy
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from bench import spec  # noqa: E402

CONFIG = {
    "name": "tiny",
    "source": "test",
    "model": {"model_type": "granite", "hidden_size": 128,
              "intermediate_size": 256, "num_hidden_layers": 2,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 32, "vocab_size": 512, "rope_theta": 10000.0,
              "rms_norm_eps": 1e-06},
    "deployment": {"layers_published": 2, "chips": 1},
    "weights": {"logit_std": 3.0},
    "engine": {"kv_bits": 8, "block_size": 16, "page_size": 16,
               "n_pages": 96, "max_seqs": 4, "prefill_chunk": 32,
               "token_budget": 40, "max_context": 256,
               "admission": "optimistic", "prefix_cache": True},
    "correct": {"far_gap": 0.4, "far_miss_share": 0.05,
                "outside_nucleus": 0.1, "min_tokens": 8, "sample_tokens": 32},
}

MIX = {
    "kind": "open_loop", "rate_rps": 3.0, "block": 8,
    "prompt": {"median": 24, "sigma": 0.5, "min": 16, "max": 48},
    "output": {"median": 6, "sigma": 0.5, "min": 4, "max": 12},
    "sampling": {"temperature": 0.8, "top_p": 0.95, "greedy_share": 0.5},
    "ramp_s": 1, "grace_s": 20, "trace_s": 2,
}


# An open-loop cell's metric entries, as a BENCHMARK.json cell of that kind
# would list them (no such cell is in the benchmark yet).
OPEN_LOOP_METRICS = {
    "end_to_end": [{"name": n, "unit": "ms"}
                   for n in ("ttft_p90_ms", "itl_p99_ms")]
    + [{"name": "setup_s", "unit": "s"}],
    "per_layer": [{"name": n, "unit": u} for n, u in (
        ("arrival_lag_p99_ms.online", "ms"), ("queue_wait_p90_ms.online", "ms"),
        ("step_ms.online", "ms"), ("prefill_ms_per_ktok.online", "ms"),
        ("kernels_roofline.online", "%"), ("device_idle.online", "%"),
        ("mfu.online", "%"))],
}


def cell(kind="open_loop", **engine):
    cfg = copy.deepcopy(CONFIG)
    cfg["engine"].update(engine)
    mix = copy.deepcopy(MIX)
    if kind == "open_loop":
        return spec.Cell(name="tiny.open_loop", config_name="tiny",
                         traffic_name=kind, chips=1, config=cfg, traffic=mix,
                         **OPEN_LOOP_METRICS)
    mix.update(kind="backlog", n_requests=24, ramp_s=0, grace_s=0)
    mix["sampling"].update(temperature=0.0, greedy_share=1.0)
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    name = "granite-3-2b.batch"

    def ap(m):
        return "workloads" not in m or name in m["workloads"]
    return spec.Cell(name=name, config_name="tiny", traffic_name=kind,
                     chips=1, config=cfg, traffic=mix,
                     end_to_end=[m for m in bench["end_to_end"] if ap(m)],
                     per_layer=[m for m in bench["per_layer"] if ap(m)])
