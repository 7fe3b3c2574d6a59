"""Serving-slice cells: the engine with LAGS admission and the decoder
attached, driven on the host's wall clock.

The engine keeps a simulated clock (the cost model advances it even when
the device decodes), so nothing here reads it for time.  The harness calls
``Engine.submit`` and ``Engine.step`` itself, submits each request when it
is due, and stamps first tokens, later tokens and finishes by request id on
the wall clock.  A token counts as delivered in the step in which the
engine adds it to the request.

What the engine does today, and so what the cell measures: every batch
step decodes one token for all ``n_slots`` rows of the dense cache at one
shared position, as a greedy stream; no prompt is prefilled on the device
(the engine prices prefill on its cost model alone).

Every batch step must decode on the device.  The engine's dense cache stops
decoding once its position reaches ``max_len - 1``; decode work does not
depend on the position (attention reads the whole cache under a length
mask), so before that step the harness sets the position back to 0 and
starts a new segment of the token stream.  A step that still ran no device
decode, or whose logits were not finite, fails the requests it served.
Every read and write of the engine's private state is in ``EngineDriver``.

The configuration file decides the model: its ``model`` keys, and the
first entry of its ``reference`` list, the model's reference module
(``Model`` lists what that module provides).  A new model configuration
adds its file and its module; nothing here changes.

After the window closes the run checks what the timed path produced:

* ``logit_gap``: each row's tokens of each segment are fed again, teacher
  forced, through the model's plain float32 reference; the number is the
  widest gap by which a served token's logit lies below the reference's
  best;
* ``admission_mismatches``: at steps drawn from the seed the harness
  records the engine's state before and after the step; the LAGS reference
  (``reference/lags.py``) must give the same batch, in order;
* ``tick_state_err``: at the same steps, the tenants' load and credit
  against the reference tick.
"""
from __future__ import annotations

import contextlib
import importlib
import shutil
import sys
import time
from pathlib import Path
from typing import List

import numpy as np

from perfbench import core, traffic, tracereduce, weights
from perfbench.reference import lags

SNAPSHOTS_PER_RUN = 24
REF_ROWS = 4  # reference rows per block: the attention scores fit
SLOW_STEP_S = 0.05  # a step this slow is named on standard error
CLOCK = time.perf_counter


class Model:
    """The configuration file's model, through its reference module.

    ``ref`` is the module that the first entry of the file's ``reference``
    list names (a path from the checkout's root).  It imports nothing of
    the program and provides:

    * ``program_config(model) -> dict``: the program's ``ModelConfig``
      keywords but ``name``, for the file's ``model`` keys; it raises
      ``core.BenchError`` for a key the program cannot run;
    * ``sizes(model) -> tuple``: its static sizes, hashable;
    * ``weight_specs(sz)``: the layout of the weights made from the seed
      (``weights.WSpec`` leaves), as the program's parameter tree holds
      them;
    * ``block_gaps(params, tokens, served, *, sz, control, chunk)``: per
      position of teacher-forced rows, the gap by which the served token's
      logit lies below the reference's best, and with ``control`` the same
      gap of the token the control puts first;
    * ``decode_counts(sz, batch, pos) -> (flops, bytes)``: one decode step
      of the batch at cache position ``pos``;
    * ``token_flops(sz, pos)``: model operations of one token there.

    ``cell.overrides["model"]`` (the CPU rehearsals' smaller sizes) is
    laid over the file's ``model`` keys before any of these reads them.
    """

    def __init__(self, cell: core.Cell):
        from repro.configs.base import ModelConfig

        path = Path(cell.config["reference"][0])
        self.ref = importlib.import_module(
            ".".join(path.with_suffix("").parts))
        keys = dict(cell.config["model"], **cell.overrides.get("model", {}))
        self.sz = self.ref.sizes(keys)
        self.mcfg = ModelConfig(name=cell.config["name"],
                                **self.ref.program_config(keys))
        self.weight_specs = self.ref.weight_specs(self.sz)


class EngineDriver:
    """Every read and write of ``Engine``'s private state that the harness
    makes, in one place.  The engine has no public way to do these; an
    engine change that renames or replaces one of these attributes (ROADMAP
    R1: prefill on the device, a length per row) keeps or replaces the
    method that uses it, and the cell measures the same work.

    * ``_decode``, ``_tokens``, ``_cache`` (``warm_up``): set-up runs the
      jitted device step once, so that the window compiles nothing, then
      puts the tokens and the position back;
    * ``_cache_len`` (``next_position``): the dense cache's position, which
      the next step decodes at; read before each step for the reference's
      segments and the counts, and set back to 0 before the cache fills
      (module docstring);
    * ``_tokens`` (``served``): the tokens a decoding step served, kept for
      the check after the window;
    * ``_cache``, ``_tokens`` (``free``): dropped after the window, so that
      the reference runs with the program's state freed;
    * ``_real_decode`` (``wrap_decode``): wrapped in the traced run to mark
      the device decode with a span, and by the tests to plant faults;
    * ``_parked`` (``parked``): requests backing off after a page
      rejection; the LAGS reference admits from queues alone, so a
      recorded step with any counts as a mismatch.
    """

    def __init__(self, eng):
        self.eng = eng

    def warm_up(self, params) -> None:
        import jax
        import jax.numpy as jnp

        eng = self.eng
        eng._tokens, finite, eng._cache = eng._decode(
            params, eng._tokens, eng._cache, jnp.asarray(0))
        bool(finite)
        eng._tokens = jnp.zeros_like(eng._tokens)
        eng._cache_len = 0
        jax.block_until_ready(eng._cache)

    def next_position(self, max_len: int) -> int:
        if self.eng._cache_len >= max_len - 1:
            self.eng._cache_len = 0  # a new segment: see the module docstring
        return self.eng._cache_len

    def served(self):
        return self.eng._tokens

    def parked(self) -> int:
        return len(self.eng._parked)

    def free(self) -> None:
        self.eng._cache = None
        self.eng._tokens = None

    def wrap_decode(self, wrapper) -> None:
        """Replace the device decode by ``wrapper(inner)``."""
        self.eng._real_decode = wrapper(self.eng._real_decode)


class _Spans:
    """Benchmark spans in the profiler's trace, in the traced run only."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation("bench." + name)


def _useful(eng, tids) -> list:
    """Each tenant's service so far, as the engine's schedstats account it
    (apart from the tick's own state)."""
    ents = eng.stats.sched.entities
    return [ents[t].useful_s if t in ents else 0.0 for t in tids]


def _snap_before(drv: EngineDriver) -> dict:
    eng = drv.eng
    ts = eng.tenants
    tids = sorted(ts)
    return {
        "running": [(r.rid, r.tenant, r.done) for r in eng.running],
        "tenants": {t: (ts[t].credit, [r.rid for r in ts[t].queue])
                    for t in tids},
        "load": [ts[t].load_avg for t in tids],
        "credit": [ts[t].credit for t in tids],
        "useful": _useful(eng, tids),
        "time": eng.stats.time_s,
        "batch_steps": eng.stats.batch_steps,
        "parked": drv.parked(),
    }


def _snap_after(eng) -> dict:
    ts = eng.tenants
    tids = sorted(ts)
    return {
        "running": [r.rid for r in eng.running],
        "load": [ts[t].load_avg for t in tids],
        "credit": [ts[t].credit for t in tids],
        "useful": _useful(eng, tids),
        "time": eng.stats.time_s,
        "batch_steps": eng.stats.batch_steps,
    }


def scheduling_readings(snaps, ec: dict, tick_dtype=np.float64) -> tuple:
    """(admission mismatches, worst tick state error) over the recorded
    steps, the tick computed in ``tick_dtype``."""
    mismatches, err = 0, 0.0
    for before, after in snaps:
        if before["parked"]:
            mismatches += 1  # the reference admits from queues alone
        want = lags.admit(before, ec["n_slots"], ec["preempt_hysteresis"])
        if want != after["running"]:
            mismatches += 1
        if after["batch_steps"] == before["batch_steps"]:
            continue  # an idle step does not tick
        step_s = after["time"] - before["time"]
        frac = ((np.asarray(after["useful"]) - np.asarray(before["useful"]))
                / max(step_s, 1e-9))
        nl, nc = lags.tick(before["load"], before["credit"], frac,
                           window=ec["credit_window"],
                           halflife=ec["pelt_halflife_steps"],
                           dtype=tick_dtype)
        err = max(err, lags.state_error(after["load"], after["credit"],
                                        nl, nc))
    return mismatches, err


def token_segments(out: np.ndarray, pos: List[int]) -> List[tuple]:
    """Split the decoded stream into segments that start at position 0:
    [(inputs (B, n), served (B, n))].  Decode k read input ``out[:, k-1]``
    (zeros for the first) at position ``pos[k]`` and served ``out[:, k]``."""
    B, N = out.shape
    inputs = np.concatenate([np.zeros((B, 1), out.dtype), out[:, :-1]], 1)
    starts = [k for k in range(N) if pos[k] == 0] + [N]
    if N and starts[0] != 0:
        raise core.BenchError("the first decode of the window is not at "
                              "position 0")
    return [(inputs[:, a:b], out[:, a:b]) for a, b in zip(starts, starts[1:])
            if b > a]


def logit_gaps(model: Model, params, segs, max_len: int,
               control: bool = False):
    """Widest served-token gap over all segments and rows (and the
    control's, with ``control``)."""
    import jax.numpy as jnp

    chunk = min(128, max_len)
    L = -(-max_len // chunk) * chunk
    worst, worst_c = 0.0, 0.0
    for inputs, served in segs:
        B, n = inputs.shape
        for r0 in range(0, B, REF_ROWS):
            tok = np.zeros((REF_ROWS, L), np.int32)
            srv = np.zeros((REF_ROWS, L), np.int32)
            rows = min(REF_ROWS, B - r0)
            tok[:rows, :n] = inputs[r0:r0 + rows]
            srv[:rows, :n] = served[r0:r0 + rows]
            g, gc = model.ref.block_gaps(params, jnp.asarray(tok),
                                         jnp.asarray(srv), sz=model.sz,
                                         control=control, chunk=chunk)
            g, gc = np.asarray(g), np.asarray(gc)
            worst = max(worst, float(g[:rows, :n].max()))
            worst_c = max(worst_c, float(gc[:rows, :n].max()))
    return worst, worst_c


class Run:
    """One run of a serving cell: set-up, the window, the checks."""

    def __init__(self, cell: core.Cell, t_start: float, params=None):
        from repro.scheduler.tenant import Tenant
        from repro.serving.engine import Engine, EngineConfig

        self.cell, self.t_start = cell, t_start
        # the set-up's parts, for the run's log line on standard error
        marks = [("start", CLOCK())]  # imports and the TPU runtime's start
        self.ec = dict(cell.config["engine"], **cell.overrides.get("engine",
                                                                  {}))
        self.model = Model(cell)
        weights.check_layout(self.model.weight_specs, self.model.mcfg)
        self.params = (weights.make(self.model.weight_specs, cell.seed)
                       if params is None else params)
        marks.append(("weights", CLOCK()))
        self.traffic = traffic.serving(cell.traffic, cell.seed, cell.seconds)
        tenants = {i: Tenant(i, weight_mb=w)
                   for i, w in enumerate(self.traffic.weight_mb)}
        ec = self.ec
        self.eng = Engine(EngineConfig(
            policy=ec["policy"], n_slots=ec["n_slots"],
            max_resident=ec["max_resident"],
            preempt_hysteresis=ec["preempt_hysteresis"],
            pallas_threshold=ec["pallas_threshold"],
            credit_window=ec["credit_window"]), tenants)
        self.eng.attach_model(self.model.mcfg, self.params,
                              max_len=ec["max_len"])
        marks.append(("engine", CLOCK()))
        self.driver = EngineDriver(self.eng)
        self.kernel_tick = (ec["pallas_threshold"] > 0
                            and len(tenants) >= ec["pallas_threshold"])
        # warm up the one decode shape, and the tick kernel where the
        # tenant count puts it on the path
        self.driver.warm_up(self.params)
        if self.kernel_tick:
            from repro.sched import pallas_backend

            T = len(tenants)
            pallas_backend.tick_and_pick(
                np.zeros(T), np.zeros(T), np.zeros(T), np.zeros(T, bool),
                ec["n_slots"], window=ec["credit_window"])
        marks.append(("warm-up", CLOCK()))
        self.setup_s = marks[-1][1] - t_start
        ends = [t_start] + [t for _, t in marks]
        self.setup_parts = {k: b - a for (k, _), a, b
                            in zip(marks, ends, ends[1:])}

    # -- the window ------------------------------------------------------
    def window(self) -> dict:
        from repro.scheduler.tenant import Request

        cell, eng, st, tr = self.cell, self.eng, self.eng.stats, self.traffic
        drv = self.driver
        max_len = self.ec["max_len"]
        closed = cell.traffic["kind"] == "closed_loop"
        snap_p = min(1.0, SNAPSHOTS_PER_RUN / max(cell.seconds * 15.0, 1.0))
        snap_rng = np.random.default_rng([cell.seed % 2 ** 63, 1])
        trace_lo = self._trace_span()[0]
        spans = _Spans(cell.trace)
        if cell.trace:
            self._wrap_for_trace(spans)

        due, first, last = {}, {}, {}
        gaps: List[float] = []
        bad = set()
        step_wall, step_pos, step_rows, step_t = [], [], [], []
        toks, pos_of = [], []
        snaps = []
        lateness = []
        finished = {}  # request id -> seconds into the window
        sizes_next = {t: 0 for t in tr.sizes}
        outstanding, tokens, i, rid_next = 0, 0, 0, len(tr.arrivals)
        sched = tr.arrivals
        tracing = False

        def submit(rid, tenant, prompt, new, due_rel, now_rel):
            due[rid] = due_rel
            lateness.append(now_rel - due_rel)
            eng.submit(Request(rid, tenant, prompt, new, eng.stats.time_s))

        def refill(tenant, now_rel):
            nonlocal rid_next
            k = sizes_next[tenant]
            prompt, new = tr.sizes[tenant][k % len(tr.sizes[tenant])]
            sizes_next[tenant] = k + 1
            submit(rid_next, tenant, prompt, new, now_rel, now_rel)
            rid_next += 1

        t0 = CLOCK()
        end = t0 + cell.seconds
        if closed:
            for t in sorted(tr.sizes):
                for _ in range(tr.outstanding):
                    refill(t, 0.0)
                    outstanding += 1
        while True:
            now = CLOCK()
            if now >= end:
                break
            rel = now - t0
            if cell.trace and not tracing and rel >= trace_lo:
                self._start_trace()
                tracing, trace_t = True, spans("traced")
                trace_t.__enter__()
            while i < len(sched) and sched[i].due_s <= rel:
                a = sched[i]
                submit(a.rid, a.tenant, a.prompt_len, a.max_new, a.due_s,
                       rel)
                i += 1
                outstanding += 1
            if outstanding == 0:
                nxt = t0 + sched[i].due_s if i < len(sched) else end
                if cell.trace and not tracing:
                    nxt = min(nxt, t0 + trace_lo)  # start the trace on time
                with spans("wait"):
                    time.sleep(max(0.0, min(nxt, end) - CLOCK()))
                continue
            pos = drv.next_position(max_len)
            snap = snap_rng.random() < snap_p
            before = _snap_before(drv) if snap else None
            b0, d0 = st.batch_steps, st.device_decodes
            nf0 = st.nonfinite_decodes
            ts = CLOCK()
            with spans("engine_step"):
                eng.step()
            te = CLOCK()
            if snap:
                snaps.append((before, _snap_after(eng)))
            batch = st.batch_steps - b0
            decoded = st.device_decodes - d0
            ok = decoded == batch and st.nonfinite_decodes == nf0
            if batch:
                step_wall.append(te - ts)
                step_pos.append(pos if decoded else -1)
                step_rows.append(len(eng.running))
                step_t.append(ts - t0)
            if decoded:
                toks.append(drv.served())
                pos_of.append(pos)
            for r in eng.running:
                if not ok:
                    bad.add(r.rid)
                if r.rid in first:
                    gaps.append(te - last[r.rid])
                else:
                    first[r.rid] = te
                last[r.rid] = te
                tokens += 1
                if r.done:
                    outstanding -= 1
                    finished[r.rid] = te - t0
                    if closed:
                        refill(r.tenant, te - t0)
                        outstanding += 1
        t_close = CLOCK()
        if tracing:
            trace_t.__exit__(None, None, None)
            self._stop_trace()

        window_s = t_close - t0
        if closed:
            ttft = []
            attempted = len(due)
        else:
            in_window = [a for a in sched if a.due_s < window_s]
            attempted = len(in_window)
            ttft = [(first[a.rid] - t0 - a.due_s) if a.rid in first
                    else (t_close - t0 - a.due_s) for a in in_window]
        return dict(window_s=window_s, ttft=ttft, gaps=gaps, tokens=tokens,
                    attempted=attempted, failed=len(bad), step_wall=step_wall,
                    step_pos=step_pos, step_rows=step_rows, step_t=step_t,
                    toks=toks, pos_of=pos_of, snaps=snaps,
                    lateness=lateness, due=due, finished=finished,
                    decode_wall=list(st.decode_wall_s),
                    backoffs=st.backoffs)

    def _trace_span(self) -> tuple:
        """The traced part of the window: its last ``trace.seconds`` (at
        most a third of it), so that stopping the profiler, which takes
        seconds, falls after the close and delays no request."""
        if not self.cell.trace:
            return 0.0, 0.0
        span = min(self.cell.config["trace"]["seconds"],
                   self.cell.seconds / 3.0)
        return self.cell.seconds - span, self.cell.seconds

    def _start_trace(self):
        import jax

        self.trace_dir = core.TRACE_DIR / self.cell.workload
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(self.trace_dir))

    def _stop_trace(self):
        import jax

        jax.profiler.stop_trace()

    def _wrap_for_trace(self, spans):
        """Mark the device decode, in the traced run only."""
        def wrapper(inner_decode):
            def real_decode():
                with spans("decode"):
                    inner_decode()
            return real_decode

        self.driver.wrap_decode(wrapper)

    # -- after the window ------------------------------------------------
    def checks(self, w: dict, control: bool = False) -> dict:
        """The numbers compared, from what the window produced.  With
        ``control``, also the control's readings (``control.py``)."""
        import jax.numpy as jnp

        out = (np.asarray(jnp.concatenate(w["toks"], axis=1))
               if w["toks"] else np.zeros((self.ec["n_slots"], 0), np.int32))
        w["toks"] = None
        self.driver.free()  # the program's state, before the reference
        segs = token_segments(out, w["pos_of"])
        gap, gap_c = logit_gaps(self.model, self.params, segs,
                                self.ec["max_len"], control=control)
        mism, err = scheduling_readings(w["snaps"], self.ec)
        if w["backoffs"]:
            mism += 1  # a rejected admission: the reference has no pages
        lim = self.cell.limits
        readings = {"logit_gap": gap, "admission_mismatches": mism,
                    "tick_state_err": err}
        checks = {k: core.check(v, lim[k]) for k, v in readings.items()}
        if control:
            low = np.float32 if not self.kernel_tick else _bf16()
            _, err_c = scheduling_readings(w["snaps"], self.ec, low)
            checks["control"] = {"logit_gap": gap_c, "tick_state_err": err_c}
        checks["_counts"] = {"decodes": int(out.shape[1]),
                             "segments": len(segs),
                             "snapshots": len(w["snaps"])}
        return checks

    def record(self, w: dict, reduction) -> dict:
        return {
            "kind": "engine", "setup_s": self.setup_s,
            "window_s": w["window_s"], "ttft_s": w["ttft"],
            "gaps_s": w["gaps"], "tokens": w["tokens"],
            "step_wall_s": w["step_wall"], "step_pos": w["step_pos"],
            "step_rows": w["step_rows"], "step_t": w["step_t"],
            "decode_wall_s": w["decode_wall"],
            "n_tenants": len(self.eng.tenants), "n_slots": self.ec["n_slots"],
            "kernel_tick": self.kernel_tick, "trace": reduction,
            "chips": self.cell.chips,
            **model_counts(self.model, self.ec["n_slots"], w["step_pos"],
                           w["step_t"], self._trace_span()),
        }


def model_counts(model: Model, n_slots: int, step_pos, step_t,
                 trace_span) -> dict:
    """The model's counts for the readers: ``decode_counts``, (flops,
    bytes) of each decode in the traced part of the window, and
    ``token_flops``, one token's operations at each batch step's position
    (0 for a step that did not decode)."""
    lo, hi = trace_span
    return {
        "decode_counts": [model.ref.decode_counts(model.sz, n_slots, p)
                          for p, t in zip(step_pos, step_t)
                          if p >= 0 and lo <= t < hi],
        "token_flops": [model.ref.token_flops(model.sz, p) if p >= 0 else 0
                        for p in step_pos],
    }


def _bf16():
    import ml_dtypes

    return ml_dtypes.bfloat16


def run(cell: core.Cell, t_start: float) -> dict:
    """One run of a serving cell; returns the result line's fields."""
    r = Run(cell, t_start)
    with core.WindowWatch() as watch:
        w = r.window()
    device = core.device_info()
    device["memory_peak_bytes"] = core.memory_peak_bytes()
    reduction = None
    if cell.trace:
        reduction = _reduce_trace(r)
        device["busy_s"] = reduction["busy_s"]
        device["window_s"] = reduction["window_s"]
    lateness = w["lateness"]
    walls = w["step_wall"] or [0.0]
    k = int(np.argmax(walls))
    slow_decode = w["decode_wall"][k] if k < len(w["decode_wall"]) else 0.0
    slow_at = w["step_t"][k] if w["step_t"] else 0.0
    print(f"perfbench: {cell.workload} window {w['window_s']:.3f}s "
          f"steps {len(w['step_wall'])} tokens {w['tokens']} "
          f"requests {w['attempted']} generator late max "
          f"{max(lateness) if lateness else 0.0:.4f}s slowest step "
          f"{walls[k]:.4f}s at {slow_at:.1f}s (its decode "
          f"{slow_decode:.4f}s); {watch.line()}", file=sys.stderr)
    slow = [f"{s:.3f}s at {t:.1f}s" for t, s in zip(w["step_t"], walls)
            if s > SLOW_STEP_S]
    print(f"perfbench: {len(slow)} steps over {SLOW_STEP_S}s: "
          f"{', '.join(slow[:8])}", file=sys.stderr)
    print("perfbench: set-up {:.3f}s: {}".format(r.setup_s, ", ".join(
        f"{k} {v:.3f}s" for k, v in r.setup_parts.items())), file=sys.stderr)
    t_checks = CLOCK()
    checks = r.checks(w)
    counts = checks.pop("_counts")
    print(f"perfbench: checked {counts['decodes']} decodes of "
          f"{r.ec['n_slots']} rows in {counts['segments']} segments and "
          f"{counts['snapshots']} recorded steps in "
          f"{CLOCK() - t_checks:.1f}s", file=sys.stderr)
    rec = r.record(w, reduction)
    rec["peaks"] = core.peaks(device["kind"]) if device["platform"] == "tpu" \
        else None
    entries = cell.per_layer if cell.trace else cell.end_to_end
    return dict(correct=core.all_pass(checks), attempted=w["attempted"],
                failed=w["failed"], metrics=core.read_metrics(rec, entries),
                device=device, checks=checks,
                breakdown=reduction["breakdown"] if reduction else None)


def _reduce_trace(r: Run) -> dict:
    path = tracereduce.find_xplane(r.trace_dir)
    events = tracereduce.events_from_xplane(path)
    red = tracereduce.reduce(events, tracereduce.span_window(
        events, "bench.traced"))
    if not r.cell.keep_trace:
        shutil.rmtree(r.trace_dir, ignore_errors=True)
    return red
