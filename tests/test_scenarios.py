"""Scenario layer: spec validation, end-to-end runtime runs on every
topology, golden-metric regression locks, and the shared evaluation
path the paper-facing benchmarks route through.

The golden tests pin small fixed-seed scenario runs to checked-in
expected values (tolerance-banded AUC, exact merge counts, detection
delay/miss/FP counts) so a merge or ingest refactor cannot silently
shift the paper-facing numbers — if one of these moves, a paper table
moved with it.
"""
import jax
import numpy as np
import pytest

from repro.data.pipeline import class_subset, normalize_minmax
from repro.data.synthetic import make_har_dataset
from repro.scenarios import (
    SCENARIOS,
    ScenarioSpec,
    make_scenario,
    run_scenario,
)
from repro.scenarios.evaluate import pair_merge_eval, pattern_loss_rows

jax.config.update("jax_platform_name", "cpu")


# ------------------------------------------------------------- spec validity


def test_make_scenario_registry_and_overrides():
    for name in ("driving", "har", "mnist_like"):
        assert SCENARIOS[name]().name == name
    spec = make_scenario("har", n_devices=4, ticks=10)
    assert (spec.n_devices, spec.ticks) == (4, 10)
    with pytest.raises(ValueError, match="unknown scenario"):
        make_scenario("cifar")


@pytest.mark.parametrize("bad", [
    dict(normal_classes=()),                      # no homes
    dict(anomaly_classes=()),                     # no held-out pool
    dict(normal_classes=(0, 1), anomaly_classes=(1,)),   # overlap
    dict(normal_classes=(0, 0), anomaly_classes=(2,)),   # duplicate home
    dict(drift_frac=1.5),
    dict(drift_targets=(0,)),                     # drift into a home class
    dict(assignment="sorted"),
    dict(n_devices=0),
    dict(forget=0.0),
    dict(dataset="imagenet"),
])
def test_spec_validation_rejects(bad):
    base = dict(
        name="t", dataset="har", n_devices=4, ticks=8,
        normal_classes=(0, 1), anomaly_classes=(5,),
    )
    with pytest.raises(ValueError):
        ScenarioSpec(**{**base, **bad})


def test_build_produces_valid_feed():
    spec = make_scenario("har", n_devices=6, ticks=12, samples_per_class=40)
    sc = spec.build()
    assert sc.train.n_classes == spec.n_normal + len(spec.anomaly_classes)
    assert sc.streams.xs.shape == (6, spec.steps, sc.n_features)
    feed = sc.feed()
    assert feed.n_ticks == spec.ticks
    assert feed.tick_batch(0).shape == (6, spec.batch, sc.n_features)
    # eval arrays carry both classes; positives are the held-out pool
    assert set(np.unique(sc.y_eval)) == {0, 1}
    # anomaly pool held out: no pre-drift sample carries an anomaly id
    anoms = set(spec.remapped_anomaly_classes())
    for d in range(6):
        bounds = sc.streams.phase_boundaries(d)
        pre = sc.streams.pattern_of_device[d, : (bounds[1] if len(bounds) > 1
                                                 else spec.steps)]
        assert not (set(pre.tolist()) & anoms)


# ------------------------------------------------- end-to-end runtime green


@pytest.mark.parametrize("topology", ["ring", "star"])
@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_scenarios_run_end_to_end(scenario, topology):
    """Every registered scenario runs green through ``FleetRuntime`` on
    ring + star: ≥1 admitted merge, finite AUCs, compile-once (asserted
    inside run_scenario), cooperative updates not worse than local
    training on the clean fleet."""
    spec = make_scenario(scenario, n_devices=6, ticks=40)
    res = run_scenario(spec, topology, merge_every=8)
    s = res.auc_summary()
    assert res.merges >= 1
    assert all(np.isfinite(v) for v in s.values()), s
    assert res.comm_bytes > 0
    assert all(v == 1 for v in res.jit_cache_sizes.values())


def test_hierarchical_and_all_to_all_topologies_also_run():
    # D=16 gives the hierarchical default two location clusters
    spec = make_scenario("har", n_devices=16, ticks=24)
    for topo in ("hierarchical", "all_to_all"):
        res = run_scenario(spec, topo, merge_every=8)
        assert res.merges >= 1


# -------------------------------------------------------- golden regression


# Checked-in expected metrics of small fixed-seed runs (ring, hops=1,
# merge_every=16, key_seed=0). AUC bands are ±0.03 (float noise across
# BLAS builds); merge/detection counts are exact. If one of these
# moves, a paper-facing number moved with it — regenerate ONLY after
# confirming the shift is intended (see benchmarks/paper_eval.py).
GOLDEN_SIZES = {
    "driving": dict(n_devices=8, ticks=80),
    "har": dict(n_devices=12, ticks=80),
    "mnist_like": dict(),                   # preset size (D=16)
}
# har and mnist_like were re-locked under jax's default
# (threefry-partitionable) PRNG stream, which draws a different SLFN
# basis from key_seed=0 than the stream they were first locked under:
# har merged 0.8179 -> 0.8327 and one clean device crosses its band
# once (fp 0 -> 1); mnist_like local 0.6367 -> 0.6529, merged 0.7608 ->
# 0.7980, clean 0.8185 -> 0.8712, delay 1.75 -> 1.5 ticks.
GOLDEN = {
    "driving": dict(local=1.0000, merged=1.0000, clean=1.0000, merges=5,
                    delay=0.0, missed=0, fp=0, events=2),
    "har": dict(local=0.8521, merged=0.8327, clean=1.0000, merges=5,
                delay=0.0, missed=0, fp=1, events=3),
    "mnist_like": dict(local=0.6529, merged=0.7980, clean=0.8712, merges=5,
                       delay=1.5, missed=0, fp=0, events=4),
}
AUC_BAND = 0.03
DELAY_BAND = 1.0


@pytest.mark.parametrize("scenario", sorted(GOLDEN))
def test_golden_scenario_metrics(scenario):
    res = run_scenario(
        make_scenario(scenario, **GOLDEN_SIZES[scenario]),
        "ring", merge_every=16, key_seed=0,
    )
    g = GOLDEN[scenario]
    s = res.auc_summary()
    assert abs(s["local_auc_mean"] - g["local"]) <= AUC_BAND, s
    assert abs(s["merged_auc_mean"] - g["merged"]) <= AUC_BAND, s
    assert abs(s["clean_merged_auc_mean"] - g["clean"]) <= AUC_BAND, s
    assert res.merges == g["merges"]
    d = res.detection
    assert d["n_drift_events"] == g["events"]
    assert len(d["missed"]) == g["missed"], d
    assert len(d["false_positives"]) == g["fp"], d
    assert abs(d["delay_mean"] - g["delay"]) <= DELAY_BAND, d


def test_golden_quantized_comm_ratio():
    """int8 wire-format lock on the driving golden run: the SAME merge
    cadence and in-band AUC at ≥3.8× fewer merge bytes than f32. This
    is the codec half of the paper-eval ≥60× comm-vs-FedAvg claim (the
    FedAvg half is history-gated by benchmarks/paper_eval.py)."""
    spec = make_scenario("driving", **GOLDEN_SIZES["driving"])
    f32 = run_scenario(spec, "ring", merge_every=16, key_seed=0)
    q = run_scenario(
        spec, "ring", merge_every=16, key_seed=0, payload_precision="int8"
    )
    g = GOLDEN["driving"]
    assert q.payload_precision == "int8"
    assert q.merges == f32.merges == g["merges"]
    assert abs(float(q.merged_aucs.mean()) - g["merged"]) <= AUC_BAND
    ratio = f32.comm_bytes / q.comm_bytes
    assert ratio >= 3.8, (f32.comm_bytes, q.comm_bytes)


# --------------------------------------------------------- shared eval path


def _two_devices():
    from repro.core import ae_train_stream, init_autoencoder

    ds = normalize_minmax(make_har_dataset(seed=0, samples_per_class=60))
    test = class_subset(ds, (3, 4, 5))
    key = jax.random.PRNGKey(0)
    devs = []
    for pat in (0, 1):   # remapped sitting / standing
        x = test.pattern(pat)
        st = init_autoencoder(key, ds.n_features, 8, x[:24], ridge=1e-2,
                              activation="identity")
        devs.append(ae_train_stream(st, x[24:]))
    return devs, test


def test_pair_merge_eval_lifts_auc():
    """The shared two-device path reproduces the paper's core effect:
    merging B into A lifts A's AUC on the {p_A, p_B} protocol."""
    (dev_a, dev_b), test = _two_devices()
    before, after = pair_merge_eval(dev_a, dev_b, test, (0, 1), seed=0)
    assert 0.0 <= before <= 1.0 and 0.0 <= after <= 1.0
    assert after >= before - 0.02


def test_pattern_loss_rows_transfer():
    """Loss rows: A inherits B's competence on B's pattern."""
    (dev_a, dev_b), test = _two_devices()
    rows = pattern_loss_rows(dev_a, dev_b, test)
    p_b = test.class_names[1]
    assert rows[p_b]["A_after"] < rows[p_b]["A_before"] + 1e-9
    assert set(rows) == set(test.class_names)


# ------------------------------------------------------------ full grid (slow)


@pytest.mark.slow
def test_paper_eval_full_grid():
    """The full topology grid (bigger fleets, all four topologies) —
    CI runs the smoke grid; this is the `-m slow` long-form."""
    from benchmarks.paper_eval import FULL_TOPOLOGIES, check_claims, run_bench

    report = run_bench(smoke=False)
    claims = check_claims(report, FULL_TOPOLOGIES)
    assert claims["all_green"], claims["green"]
    assert claims["auc_and_comm_scenarios"], report["scenarios"]
