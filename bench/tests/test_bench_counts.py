"""The FLOP/byte count functions against counts made by hand, and the
metric arithmetic of the jobs (window rates, percentiles over all samples)."""
import numpy as np
import pytest

from bench import counts
from bench.geometry.dense import Geometry

# d 8, 2 heads of 4 (1 kv head), ff 16, SwiGLU, vocab 32, 2 layers
G = Geometry(n_layers=2, d=8, heads=2, kv_heads=1, head_dim=4, ff=16,
             vocab=32, gated=True, n_classes=3)
P = 64 + 32 + 32 + 64 + 256 + 128       # a layer's product weights


def test_projection_and_layer_params_by_hand():
    # wq 8x8, wk 8x4, wv 8x4, wo 8x8, wi 8x32, wo 16x8
    for l in range(2):
        assert sum(a * b for a, b in G.projections(l)) == P
        assert G.matmul_flops(l) == 2 * P
        assert G.layer_params(l) == P + 16
        assert G.cache_bytes(l) == 2 * 1 * 4 * 2
    assert G.head_params == 32 * 8 + 8


def test_fl_round_flops_by_hand():
    masks = np.array([[0, 1], [0, 0]], np.float32)   # client 0 trains layer 1
    seq, tau, batch = 4, 2, 3
    fwd_tok = 2 * P + 4 * seq * 2 * 4
    bwd_tok = 2 * P + 8 * seq * 2 * 4
    upd_tok = tau * batch * seq
    want = 0.0
    for sel in ([1], []):
        want += upd_tok * 2 * fwd_tok + tau * batch * 2 * 8 * 3
        if sel:
            want += upd_tok * ((2 - sel[0]) * bwd_tok + len(sel) * 2 * P)
    probe_tok = batch * seq
    want += 1 * (probe_tok * 2 * (fwd_tok + bwd_tok + 2 * P) + batch * 2 * 8 * 3)
    want += 5 * (seq * 2 * fwd_tok + 2 * 8 * 3)
    got = counts.fl_round_flops(G, masks, 1, tau=tau, batch=batch, seq=seq,
                                eval_rows=5)
    assert got == pytest.approx(want)


def test_kernel_bytes_by_hand():
    masks = np.array([[1, 1], [0, 1]], np.float32)
    assert counts.masked_update_bytes(G, masks, tau=2) == 2 * 3 * (P + 16) * 3 * 2
    assert counts.layer_grad_norm_bytes(G, probed=3) == 3 * 2 * (P + 16) * 2


def test_decode_step_counts_by_hand():
    layers = [np.array([0]), np.array([0, 1])]
    active = [(0, 3), (1, 0), (-1, 5)]
    flops = sum(2 * (2 * P + 4 * (p + 1) * 8) + 2 * 8 * 32 for _, p in active)
    flops += (1 + 2) * 2 * P
    kv = 2 * 1 * 4 * 2
    nbytes = (2 * (P + 16) + 32 * 8 + 8) * 2
    nbytes += sum(2 * kv * (p + 2) for _, p in active)
    nbytes += (1 + 2) * (P + 16) * 2
    f, b = counts.decode_step_counts(G, active, layers)
    assert f == pytest.approx(flops) and b == pytest.approx(nbytes)
    f0, b0 = counts.decode_step_counts(G, active, None)
    assert f0 == pytest.approx(flops - 3 * 2 * P)


def test_delta_matmul_counts_by_hand():
    layers = [np.array([0]), np.array([0, 1])]
    out = counts.delta_matmul_counts(G, [(0, 1), (1, 2)], layers)
    assert len(out) == 2 * 6
    a, b = G.projections(0)[0]                    # layer 0, wq: 2 entries
    assert out[0] == (2.0 * 2 * a * b + 2.0 * 2 * a * b,
                      (2 * a + a * b + 2 * a * b + 2 * b) * 2)
    assert out[6][0] == 2.0 * 2 * a * b + 2.0 * 1 * a * b   # layer 1: 1 entry


def test_roofline_picks_the_binding_peak():
    from bench.peaks import roofline_seconds
    t, bound = roofline_seconds(197e12, 1.0, "TPU v5 lite")
    assert bound == "flops" and t == pytest.approx(1.0)
    t, bound = roofline_seconds(1.0, 819e9, "TPU v5 lite")
    assert bound == "bytes" and t == pytest.approx(1.0)
    with pytest.raises(KeyError):
        roofline_seconds(1.0, 1.0, "TPU v9 imaginary")


def test_percentile_over_all_samples():
    from bench.jobs.serve import percentile
    vals = list(range(1, 101))
    assert percentile(vals, 90) == pytest.approx(90.1)
    assert percentile(vals, 50) == pytest.approx(50.5)
    assert percentile([7.0], 90) == 7.0


def test_window_numbers_count_due_requests_and_waits():
    from bench.jobs.serve import window_numbers

    class Gen(list):
        def __init__(self, times):
            super().__init__(range(len(times)))
            self.times = times

    class Req:
        def __init__(self, times):
            self.generated = Gen(times)

    class Loop:
        w0, w1 = 10.0, 20.0
        completions = [9.0, 11.0, 12.0, 25.0]

    # two slots: request j >= 2 is due at completion j - 2
    reqs = [Req([1.0, 9.0]), Req([2.0, 11.0]), Req([5.0, 9.5]),
            Req([11.5, 13.0]), Req([14.0, 15.0]), Req([])]
    nums = window_numbers(Loop, reqs, 2)
    # tokens stamped in [10, 20]: 11, 11.5, 13, 14, 15
    assert nums["tokens"] == 5
    # due in window: req 3 (due 11, first 11.5), req 4 (due 12, first 14)
    assert nums["ttft_s"] == pytest.approx([0.5, 2.0])
    # req 5 is due at 25, after the window: not counted
    assert nums["admitted"] == 2


def test_a_request_still_waiting_counts_its_wait():
    from bench.jobs.serve import window_numbers

    class Gen(list):
        times = []

    class Req:
        generated = Gen()

    class Loop:
        w0, w1 = 0.0, 10.0
        completions = [4.0]

    nums = window_numbers(Loop, [Req(), Req()], 1)
    assert nums["ttft_s"] == [6.0]
