"""Batched serving driver: continuous request loop with KV caches and
the paper's OS-ELM drift monitor scoring every batch.

The monitor is the resident runtime's sequential detector
(``repro.runtime.detector``) run at n_devices=1: the OS-ELM
autoencoder is warmed up on the first batch's features BEFORE any
score is taken (an untrained detector's round-0 score is
meaningless), every round's features are scored exactly once against
the current detector, and the EWMA/threshold detector turns the raw
score trajectory into an explicit DETECTED flag.

With ``--telemetry-dir`` the loop emits through a ``repro.obs``
``TelemetrySink``: per-round latency/score series as spans in
``trace.jsonl``, round counters and the drift gauge in
``exposition.txt``, and a summary line at exit.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --reduced \
        --rounds 4 --batch 4 --prompt-len 64 --new-tokens 16

``--fleet`` switches to the async fleet ingress driver
(``repro.launch.serve_fleet``): concurrent synthetic clients streaming
per-device samples through a ``ServeFrontend`` in front of a resident
``FleetRuntime`` — the serving-under-load path the README documents.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import ae_score, ae_train_stream, init_autoencoder, oselm_step
from repro.models import decode_step, encoder_forward, init_params, prefill
from repro.obs import TelemetryConfig, TelemetrySink
from repro.runtime import DetectorConfig, detector_update, init_detector


def main() -> None:
    if "--fleet" in sys.argv[1:]:
        # the async fleet-ingress driver owns its own arg surface
        from repro.launch.serve_fleet import main as fleet_main

        sys.argv.remove("--fleet")
        sys.exit(fleet_main())
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--drift-round", type=int, default=-1,
                    help="inject a shifted-distribution batch at this round")
    ap.add_argument("--seed", type=int, default=0,
                    help="base PRNG seed (prompts, params, drift injection)")
    ap.add_argument("--telemetry-dir", default=None,
                    help="emit trace.jsonl/exposition.txt into this directory")
    args = ap.parse_args()
    # a zero-round or zero-batch run would exit silently green — make
    # the misconfiguration loud instead
    if args.rounds < 1:
        ap.error(f"--rounds must be >= 1 (got {args.rounds}): a zero-round "
                 "serving loop does nothing")
    if args.batch < 1:
        ap.error(f"--batch must be >= 1 (got {args.batch}): every round "
                 "serves at least one request")

    sink = (
        TelemetrySink(TelemetryConfig(dir=args.telemetry_dir))
        if args.telemetry_dir else None
    )
    if sink is not None:
        rounds_total = sink.registry.counter(
            "serve_rounds_total", "serving rounds completed"
        )
        round_seconds = sink.registry.histogram(
            "serve_round_seconds", "wall-clock per serving round"
        )
        tokens_total = sink.registry.counter(
            "serve_tokens_total", "tokens decoded"
        )
        drift_score = sink.registry.gauge(
            "serve_drift_score", "monitor's latest mean ae_score"
        )
        drift_flags = sink.registry.counter(
            "serve_drift_flags_total", "rounds the monitor flagged"
        )

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    key = jax.random.PRNGKey(args.seed)
    params = init_params(key, cfg)
    B, S = args.batch, args.prompt_len
    max_seq = S + args.new_tokens
    drift_round = args.drift_round if args.drift_round >= 0 else args.rounds - 1

    fe = None
    if cfg.frontend is not None:
        fe = jax.random.normal(key, (B, cfg.n_frontend_tokens, cfg.d_frontend))
    enc_out = encoder_forward(params, cfg, fe) if fe is not None else None

    prefill_fn = jax.jit(
        lambda p, t, f: prefill(p, cfg, t, frontend=f, cache_len=max_seq)
    )
    decode_fn = jax.jit(
        lambda p, t, c, pos, e: decode_step(p, cfg, t, c, pos, enc_out=e, max_seq=max_seq)
    )

    # Warm up the monitor BEFORE the serving loop: prefill a couple of
    # in-distribution batches the loop will never serve, and train the
    # detector on their features. Round 0 is then scored OUT-of-sample
    # against a calibrated detector — previously the first round scored
    # the very features the detector had just been initialized on, so
    # the round-0 "drift score" was trivially ~0 and poisoned the
    # monitor's baseline.
    warm_feats = []
    for w in range(2):
        kw = jax.random.fold_in(key, 10_000 + w)  # disjoint from round keys
        wp = jax.random.randint(kw, (B, S), 0, cfg.vocab)
        _, _, f = prefill_fn(params, wp, fe)
        warm_feats.append(f)
    warm = jnp.concatenate(warm_feats)
    detector = init_autoencoder(
        jax.random.fold_in(key, 7), cfg.d_model, cfg.detector_hidden,
        jnp.tile(warm, (2 * cfg.detector_hidden // warm.shape[0] + 1, 1)),
        activation="identity", ridge=1e-2,
    )
    detector = ae_train_stream(detector, warm)

    monitor = init_detector(1)
    mon_cfg = DetectorConfig(alpha=0.7, k_sigma=4.0, warmup=2, patience=1)
    for rnd in range(args.rounds):
        k = jax.random.fold_in(key, rnd)
        prompts = jax.random.randint(k, (B, S), 0, cfg.vocab)
        if rnd == drift_round:  # distribution shift: permuted vocabulary
            prompts = (prompts * 31 + 17) % cfg.vocab

        t0 = time.time()
        span = (
            sink.span("serve_round", seq=rnd)
            if sink is not None else contextlib.nullcontext()
        )
        with span:
            logits, caches, features = prefill_fn(params, prompts, fe)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            for i in range(args.new_tokens):
                logits, caches = decode_fn(
                    params, tok, caches, jnp.asarray(S + i, jnp.int32), enc_out
                )
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
            jax.block_until_ready(tok)
        dt = time.time() - t0

        # single scoring site: every round (incl. round 0) is scored
        # against the current detector, THEN the detector trains on it
        score = float(ae_score(detector, features).mean())
        monitor, flagged, _ = detector_update(
            monitor, jnp.asarray([score]), mon_cfg
        )
        detector = oselm_step(detector, features, features)
        if sink is not None:
            rounds_total.inc()
            round_seconds.observe(dt)
            tokens_total.inc(B * args.new_tokens)
            drift_score.set(score)
            if bool(flagged[0]):
                drift_flags.inc()
        flag = "  << DRIFT" if rnd == drift_round else ""
        if bool(flagged[0]):
            flag += "  [DETECTED]"
        print(
            f"round {rnd}: {B} reqs × {args.new_tokens} tok in {dt:.2f}s "
            f"({B*args.new_tokens/dt:.1f} tok/s) drift_score={score:.5f}{flag}"
        )

    if sink is not None:
        sink.close()
        print("telemetry:", json.dumps({
            "dir": args.telemetry_dir,
            "rounds": int(rounds_total.value),
            "tokens": int(tokens_total.value),
            "drift_flags": int(drift_flags.value),
        }))


if __name__ == "__main__":
    main()
