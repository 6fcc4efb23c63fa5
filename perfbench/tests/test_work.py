"""The work model against a hand count at one shape."""
import tiny
from bench import spec, work

granite = spec.load_model(tiny.CONFIG)
PK = {"bf16_flops": 100.0, "int8_ops": 200.0, "hbm_bytes_s": 10.0}
DIMS = granite.Dims(n_layers=2, n_layers_published=2, d_model=8,
                    n_heads=2, n_kv_heads=1, head_dim=4, d_ff=16, vocab=10,
                    rope_theta=1e4, rms_eps=1e-6, block_size=4, kv_bits=8,
                    logit_std=1.0)


def test_gemm_hand_count():
    ops, t = work.gemm(3, 8, 16, PK)
    assert ops == 2 * 3 * 8 * 16
    byts = 8 * 16 / 2 + 4 * 16 + 3 * 8 + 8 * 3 + 4 * 3 * 16
    assert t == max(ops / 200.0, byts / 10.0)


def test_attention_hand_count():
    # one sequence: 2 rows at positions 5 and 6 attend 6 + 7 keys; 7 walked
    fl, t = work.attention([(2, 13, 7)], DIMS.n_heads, DIMS.n_kv_heads,
                           DIMS.head_dim, PK)
    assert fl == 4 * 2 * 4 * 13
    byts = 7 * 1 * (2 * 4 + 16) + 2 * 2 * 4 * 6
    assert t == max(fl / 100.0, byts / 10.0)


def test_decode_dispatch_hand_count():
    w = granite.dispatch(1, [(1, 5, 5)], 1, DIMS, PK)
    per_layer_ops = 2 * (8 * 8 + 8 * 4 + 8 * 4 + 8 * 8 + 3 * 8 * 16)
    assert w.int8_ops == per_layer_ops * 2
    head = 2 * 8 * 10
    assert w.bf16_flops == 4 * 2 * 4 * 5 * 2 + head
    assert w.dispatches == 1


def test_prefill_step_splits_chunks():
    from bench.driver import StepRecord
    s = StepRecord(0.0, 1.0, decode_ctx=[], prefill=[(0, 10, True)],
                   prompt_rows=10)
    w = granite.step_work(s, DIMS, PK, chunk=4)
    assert w.dispatches == 3                       # 4 + 4 + 2 rows
    keys = sum(p + 1 for p in range(10))
    assert w.bf16_flops == 4 * 2 * 4 * keys * 2 + 2 * 8 * 10
