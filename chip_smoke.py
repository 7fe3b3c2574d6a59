#!/usr/bin/env python3
"""Bring-up check on one TPU: the main path runs on the chip and agrees with
its references.

  python chip_smoke.py

Phases, each through the entry point a user calls, each printing one line
with its sizes, what it compared and its wall time (compile apart from the
steady state):

  serve-model        ``launch/serve.py --real-model`` with stablelm-1.6b at
                     full width and depth (bf16, random weights from the
                     seed): every batch step decodes on the device, with
                     finite logits.
  model-consistency  teacher-forced ``decode_step`` over a prompt against
                     ``prefill`` on the same prompt, same full-width model.
  serve-pallas-tick  ``launch/serve.py`` with 1024 tenants, so the engine's
                     Load-Credit tick runs the compiled ``lags_select``
                     kernel; the kernel against the float64 numpy oracle at
                     1k, 16k and 64k tenants.
  fleet-scan         the Fig 7 fleet (800 functions on 10 nodes) on the
                     vmapped ``lax.scan`` backend against the numpy backend.

Refuses to run without a TPU.  One process; no phase catches an exception.
The last line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import setup_compile_cache  # noqa: E402

ARCH = "stablelm-1.6b"
SLOTS = 16
MAX_LEN = 1024
# simulated seconds of serving: the seed-0 workload runs 191 batch steps,
# well inside the 1023 decodes a 1024-token cache holds
SERVE_DURATION_S = 2.0
PROMPT = 32
# bf16 decode-vs-prefill agreement, as the relative RMS error
# ||decode - prefill|| / ||prefill|| over all logits of the batch.  The two
# paths round bf16 activations at different points through 24 layers: a CPU
# run of the same family in bf16 at 24 layers (d_model 256 and 512) gives
# 0.05, while a decode that ignores its cache gives 1.4.  The bound sits at
# 4x the first and 7x below the second.
CONSISTENCY_TOL = 0.2
TICK_SIZES = (1024, 16384, 65536)
TICK_K = 16
FLEET_FNS, FLEET_NODES = 800, 10
# Fig 7's own horizon (repro.fleet.consolidate.CLUSTER_DURATION_S): burst
# backlogs drain inside it; the numpy reference takes seconds because nodes
# with equal function counts share one simulation
FLEET_DURATION_S = 60.0


def check(ok: bool, phase: str, what: str) -> None:
    """A phase's result disagreed with its reference: exit nonzero."""
    if not ok:
        raise SystemExit(f"chip_smoke: {phase}: FAILED: {what}")


def phase_line(name: str, **fields) -> None:
    print(f"[{name}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def serve_model(arch: str, reduced: bool, slots: int, max_len: int,
                duration_s: float):
    """``serve.py --real-model``: every batch step decodes on the device."""
    from repro.launch import serve

    t0 = time.perf_counter()
    st = serve.main([
        "--real-model", "--arch", arch, *(["--reduced"] if reduced else []),
        "--slots", str(slots), "--max-len", str(max_len), "--tenants", "48",
        "--seed", "0", "--duration", str(duration_s),
    ])
    wall = time.perf_counter() - t0
    name = "serve-model"
    check(0 < st.batch_steps < max_len - 1, name,
          f"{st.batch_steps} batch steps do not fit a {max_len}-token cache")
    check(st.device_decodes == st.batch_steps, name,
          f"device decoded {st.device_decodes} of {st.batch_steps} steps")
    check(st.nonfinite_decodes == 0, name,
          f"{st.nonfinite_decodes} decodes had non-finite logits")
    steady = sorted(st.decode_wall_s[1:])
    phase_line(
        name, model=arch + ("-reduced" if reduced else ""), slots=slots,
        max_len=max_len, sim_s=duration_s, batch_steps=st.batch_steps,
        device_decodes=st.device_decodes, logits="finite",
        first_decode_with_compile_s=f"{st.decode_wall_s[0]:.3f}",
        median_decode_ms=f"{steady[len(steady) // 2] * 1e3:.3f}"
        if steady else "n/a",
        decode_total_s=f"{sum(st.decode_wall_s):.2f}",
        wall_s=f"{wall:.2f}",
    )


def model_consistency(cfg, batch: int, prompt: int, tol: float):
    """Teacher-forced decode over a prompt must match ``prefill``'s last
    logits (the model smoke test's check, at this config's size)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import model

    name = "model-consistency"
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (batch, prompt), 0,
                              cfg.vocab_size)
    prefill = jax.jit(
        lambda p, t: model.prefill(p, cfg, {"tokens": t}, max_len=prompt)[0])
    decode = jax.jit(lambda p, t, c, n: model.decode_step(
        p, cfg, {"tokens": t}, c, n))

    t0 = time.perf_counter()
    want = prefill(params, toks).block_until_ready()
    prefill_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    want = prefill(params, toks).block_until_ready()
    prefill_steady_s = time.perf_counter() - t0

    cache = model.init_cache(cfg, batch, prompt)
    times = []
    got = None
    for t in range(prompt):
        t0 = time.perf_counter()
        got, cache = decode(params, toks[:, t:t + 1], cache,
                            jnp.asarray(t, jnp.int32))
        got.block_until_ready()
        times.append(time.perf_counter() - t0)
    want = np.asarray(want, np.float32)
    got = np.asarray(got, np.float32)
    check(np.isfinite(want).all() and np.isfinite(got).all(), name,
          "non-finite logits")
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    top1 = float(np.mean(got.argmax(-1) == want.argmax(-1)))
    check(rel <= tol, name,
          f"relative RMS error of decode vs prefill {rel:.4g} > {tol}")
    steady = sorted(times[1:])
    phase_line(
        name, model=cfg.name, dtype=cfg.dtype, layers=cfg.n_layers,
        d_model=cfg.d_model, batch=batch, prompt=prompt,
        compared="decode_step-vs-prefill-last-logits",
        rel_rms_err=f"{rel:.4g}", tol=tol,
        max_abs_err=f"{float(np.max(np.abs(got - want))):.4g}",
        top1_agree=f"{top1:.3f}",
        prefill_with_compile_s=f"{prefill_s:.3f}",
        prefill_steady_s=f"{prefill_steady_s:.4f}",
        first_decode_with_compile_s=f"{times[0]:.3f}",
        median_decode_ms=f"{steady[len(steady) // 2] * 1e3:.3f}",
    )


def serve_pallas_tick(tenants: int, duration_s: float, sizes, k: int):
    """``serve.py`` above the Pallas threshold, then the tick kernel against
    the float64 oracle at each size.  On a TPU the engine's tick must lower
    to the compiled kernel; elsewhere it runs interpreted."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.load_credit import PELT_HALFLIFE_TICKS
    from repro.launch import serve
    from repro.sched import pallas_backend as pb
    from repro.serving.engine import EngineConfig

    name = "serve-pallas-tick"
    window = EngineConfig.credit_window
    check(tenants >= EngineConfig.pallas_threshold, name,
          f"{tenants} tenants stay below the kernel threshold")
    lowered = pb.tick.lower(
        jnp.zeros(tenants, jnp.float32), jnp.zeros(tenants, jnp.float32),
        jnp.zeros(tenants, jnp.float32), jnp.zeros(tenants, bool),
        k=EngineConfig.n_slots, window=window, halflife=PELT_HALFLIFE_TICKS,
        interpret=pb.interpret_default(),
    ).as_text()
    compiled = "tpu_custom_call" in lowered
    check(compiled != pb.interpret_default(), name,
          f"tick lowers {'with' if compiled else 'without'} the TPU kernel "
          f"on {jax.default_backend()}")

    t0 = time.perf_counter()
    st = serve.main(["--tenants", str(tenants), "--seed", "0",
                     "--duration", str(duration_s)])
    serve_s = time.perf_counter() - t0
    check(st.batch_steps > 0, name, "no batch step ran the tick")

    rows = []
    rng = np.random.default_rng(7)
    for T in sizes:
        # credits distinct on a 1/16 grid; one EMA step moves each by less
        # than half the spacing, so f32 vs f64 cannot reorder the picks
        credit = rng.permutation(T) / 16.0
        load = rng.integers(0, 17, T) / 16.0
        frac = rng.integers(0, 17, T) / 16.0
        runnable = rng.random(T) < 0.7
        t0 = time.perf_counter()
        nl, nc, idx = pb.tick_and_pick(load, credit, frac, runnable, k,
                                       window=window)
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        pb.tick_and_pick(load, credit, frac, runnable, k, window=window)
        steady_s = time.perf_counter() - t0
        rl, rc, ridx = pb.numpy_reference(load, credit, frac, runnable, k,
                                          window=window)
        check(idx.tolist() == ridx.tolist(), name, f"T={T}: picks differ")
        check(np.allclose(nl, rl, rtol=1e-5, atol=1e-6)
              and np.allclose(nc, rc, rtol=1e-5, atol=1e-6), name,
              f"T={T}: credit state differs from the float64 oracle")
        rows.append(f"T{T}:first={first_s:.3f}s,steady={steady_s * 1e3:.3f}ms")
    phase_line(
        name, tenants=tenants, sim_s=duration_s, batch_steps=st.batch_steps,
        tick="compiled-kernel" if compiled else "interpreted",
        serve_wall_s=f"{serve_s:.2f}", k=k,
        compared="picks-equal,state-rtol1e-5-vs-float64",
        tick_and_pick=";".join(rows),
    )


def fleet_scan(n_fns: int, n_nodes: int, duration_s: float):
    """The vmapped scan fleet against the numpy fleet, same placement and
    seed, with the backend-differential tolerances of the fleet tests."""
    from repro.fleet import make_policy, place, simulate_fleet
    from repro.fleet.consolidate import CLUSTER_EXEC_S

    name = "fleet-scan"
    asg = place("round-robin", n_fns, n_nodes, policy=make_policy("lags"),
                exec_s=CLUSTER_EXEC_S, seed=7)
    kw = dict(duration_s=duration_s, exec_s=CLUSTER_EXEC_S, seed=7)
    t0 = time.perf_counter()
    ref = simulate_fleet("lags", asg, threads_per_fn=8, **kw)
    ref_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    jx = simulate_fleet("lags", asg, backend="jax", **kw)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    jx = simulate_fleet("lags", asg, backend="jax", **kw)
    steady_s = time.perf_counter() - t0
    check(ref.n_completed > 0, name, "numpy fleet completed nothing")
    check(abs(jx.n_completed - ref.n_completed)
          <= max(6, 0.05 * ref.n_completed), name,
          f"completions {jx.n_completed} vs {ref.n_completed}")
    check(abs(jx.pct(50) - ref.pct(50)) < 0.25 * max(ref.pct(50), 0.05),
          name, f"p50 {jx.pct(50):.4f} vs {ref.pct(50):.4f}")
    check(abs(jx.overhead_frac - ref.overhead_frac) < 0.05, name,
          f"overhead {jx.overhead_frac:.4f} vs {ref.overhead_frac:.4f}")
    phase_line(
        name, fns=n_fns, nodes=n_nodes, sim_s=duration_s, policy="lags",
        completed=f"{jx.n_completed}/{ref.n_completed}",
        p50_s=f"{jx.pct(50):.4f}/{ref.pct(50):.4f}",
        overhead=f"{jx.overhead_frac:.4f}/{ref.overhead_frac:.4f}",
        compared="jax/numpy",
        jax_with_compile_s=f"{first_s:.2f}", jax_steady_s=f"{steady_s:.2f}",
        numpy_s=f"{ref_s:.2f}",
    )


def main() -> None:
    setup_compile_cache()
    import jax

    from repro.configs.base import get_config

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, found platform {dev.platform!r}")
    serve_model(ARCH, False, SLOTS, MAX_LEN, SERVE_DURATION_S)
    model_consistency(get_config(ARCH), SLOTS, PROMPT, CONSISTENCY_TOL)
    serve_pallas_tick(1024, SERVE_DURATION_S, TICK_SIZES, TICK_K)
    fleet_scan(FLEET_FNS, FLEET_NODES, FLEET_DURATION_S)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
