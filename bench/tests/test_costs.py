"""Operation and byte counts of costs.py against counts made by hand at
the paper's three Table 3 widths."""
import pytest

from bench import costs


@pytest.mark.parametrize("n,h,act,flops", [
    # score 2nH + H + aH + 2Hm + 3m, step 6H^2 + 4Hm + 3H + m + 1, m = n
    (561, 128, "identity", 143616 + 128 + 143616 + 1683 + 98304 + 287232 + 384 + 561 + 1),
    (225, 16, "sigmoid", 7200 + 16 + 64 + 7200 + 675 + 1536 + 14400 + 48 + 225 + 1),
    (784, 64, "identity", 100352 + 64 + 100352 + 2352 + 24576 + 200704 + 192 + 784 + 1),
])
def test_sample_flops_by_hand(n, h, act, flops):
    assert costs.sample_flops(n, h, act) == flops


@pytest.mark.parametrize("n,h,window,nbytes", [
    # 2 x 4(H^2 + Hn) state, 4 T n window, 4 for the loss
    (561, 128, 32, 2 * 4 * (16384 + 71808) + 4 * 32 * 561 + 4),   # 777,348
    (225, 16, 2, 2 * 4 * (256 + 3600) + 4 * 2 * 225 + 4),          # 32,652
    (784, 64, 32, 2 * 4 * (4096 + 50176) + 4 * 32 * 784 + 4),      # 534,532
])
def test_tick_bytes_by_hand(n, h, window, nbytes):
    assert costs.tick_bytes(n, h, window) == nbytes


def test_har_tick_sizes():
    """The har T=32 tick at D=4096: about 88 GFLOP and 3.18 GB, so the
    bytes set its least time on a v5e (3.9 ms against 0.45 ms)."""
    d, t = 4096, 32
    flops = d * t * costs.sample_flops(561, 128, "identity")
    nbytes = d * costs.tick_bytes(561, 128, t)
    assert 88e9 < flops < 89e9
    assert 3.18e9 < nbytes < 3.19e9
    peaks = costs.load_peaks("TPU v5 lite")
    least, bound = costs.least_seconds(flops, nbytes, peaks)
    assert bound == "bytes" and 3.8e-3 < least < 4.0e-3


def test_merge_flops_by_hand():
    # n=3, h=2, two participants: inverse 8/3 + 16, payload + 4 + 2*4*3
    inv = 8 / 3 + 16
    payload = 2 * (inv + 4 + 24)
    solve = inv + 24
    ring = costs.merge_flops(3, 2, 2, 2, "ring", hops=1)
    assert ring == pytest.approx(payload + 2 * 2 * (4 + 6) + 2 * solve)
    star = costs.merge_flops(3, 2, 2, 2, "star")
    assert star == pytest.approx(payload + 1 * (4 + 6) + solve)


def test_steps_per_row():
    assert costs.steps_per_row({"window": 32, "batch": 2}) == 32
    assert costs.steps_per_row({"batch": 2}) == 2


def test_peaks_known_and_unknown():
    peaks = costs.load_peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in peaks.json"):
        costs.load_peaks("TPU v99")
