"""Serving cells: open-loop traffic into ``ServeEngine.submit``/``step`` on
the paged path.

Set-up makes the weights on the device from the seed, builds the engine as
the cell file says, and serves a warm-up request that samples as the
traffic does, so that the prefill chunk, the decode step, ``DecodeSync``
and sampling are compiled; ``setup_s`` ends there.  A
lead-in of the same traffic then brings the engine to its steady state,
and the window offers every request at its due time, whether or not
earlier ones have finished, and steps the engine while it has work; each
token is stamped when the step that made it returns.  Once the window
closes the check runs: a sample of the finished requests, drawn from the
seed and holding the longest one, goes through the float32 reference and
the reference's own sampling under the engine's keys
(``bench/reference/sample.py``), and the widest gap by which a served
token lies below the reference's choice is compared with the cell's
limit.
"""
from __future__ import annotations

import collections
import gc
import json
import math
import time

import numpy as np

from .. import harness as H
from ..reference import sample as ref_sample
from ._model import dims_for, model_config

#: the traced part of a ``--trace 1`` window: its last seconds
TRACE_SECONDS = 8.0
WARMUP_RID = 1 << 30


def settings(cell: H.Cell, rehearse: bool) -> tuple[dict, dict, float]:
    """``(engine, traffic, rate)``, with the cell's ``rehearsal`` overrides
    applied when rehearsing."""
    engine = dict(cell.spec["engine"])
    traffic = json.loads(json.dumps(cell.traffic))
    rate = float(cell.spec["rate_per_s"])
    if rehearse:
        r = cell.spec.get("rehearsal", {})
        engine.update(r.get("engine", {}))
        for k, v in r.get("traffic", {}).items():
            traffic[k].update(v) if isinstance(v, dict) else traffic.__setitem__(k, v)
        rate = float(r.get("rate_per_s", rate))
    return engine, traffic, rate


class Rec:
    """One offered request as the harness saw it (perf_counter times)."""
    __slots__ = ("off", "req", "due", "submit", "admit", "tokens", "error")

    def __init__(self, off, req, due):
        self.off, self.req, self.due = off, req, due
        self.submit = self.admit = None
        self.tokens: list[float] = []
        self.error = None


def _instrument(eng, spans: H.Spans) -> None:
    """Host spans around the calls into each layer of the engine."""
    eng._prefill_step = spans.wrap("bench.prefill", eng._prefill_step)
    eng._decode_step = spans.wrap("bench.decode", eng._decode_step)
    eng._sample_one = spans.wrap("bench.sample", eng._sample_one)
    eng.decode_sync.step = spans.wrap("bench.sync", eng.decode_sync.step)


def _warm(eng, Request, traffic: dict, vocab: int) -> None:
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, vocab, eng.prefill_chunk + 1).astype(np.int32)
    eng.run([Request(WARMUP_RID, prompt, max_new_tokens=3,
                     temperature=float(traffic["temperature"]),
                     top_k=int(traffic["top_k"]))])


def _mark_programs(eng, spans: H.Spans) -> None:
    """Call the prefill and decode programs once each, each inside a span
    of its own and waited for, so the trace reader can tell their module
    executions apart.  All rows point at the null block, which no request
    reads."""
    import jax
    import jax.numpy as jnp
    sched = eng.scheduler
    with spans("bench.mark.prefill"):
        logits, eng._pages = eng._prefill_chunk_fn(
            eng.params, jnp.zeros((1, eng.prefill_chunk), jnp.int32),
            eng._pages, jnp.zeros((1, sched.table_width), jnp.int32),
            jnp.int32(0))
        jax.block_until_ready(logits)
    B = eng.max_batch
    with spans("bench.mark.decode"):
        logits, eng._pages = eng._decode_paged(
            eng.params, jnp.zeros((B, 1), jnp.int32), eng._pages,
            jnp.zeros((B, sched.table_width), jnp.int32),
            jnp.zeros((B,), jnp.int32))
        jax.block_until_ready(logits)


#: ``engine.stats`` entries whose window deltas the ``window`` line prints
STATS = ("decode_rows", "prefill_positions", "sampled_rows")


def program_counters(eng) -> dict:
    """The program's counters: ``STATS`` of ``engine.stats`` and
    ``DecodeSync``'s ``calls`` and ``compiles``."""
    return {**{k: eng.stats[k] for k in STATS},
            "decode_sync.calls": eng.decode_sync.calls,
            "decode_sync.compiles": eng.decode_sync.compiles}


def _window(run: H.Run, eng, Request, offered, lead_in_s: float) -> list[Rec]:
    """Serve ``offered`` from ``lead_in_s`` before the window to its close.
    Returns every request offered by the close; ``run.window`` is the
    measured part, ``run.records["lead_in_start"]`` where the lead-in
    began, and ``run.records["counters"]`` the program's counters' deltas
    over the window."""
    spans = run.spans
    recs = []
    run.records["lead_in_start"] = time.perf_counter()
    t0 = run.records["lead_in_start"] + lead_in_s
    end = t0 + run.seconds
    run.window = (t0, end)
    pending = collections.deque(offered)
    live: dict[int, Rec] = {}
    traced = False
    trace_from = end - min(TRACE_SECONDS, run.seconds)
    steps = 0
    opened = None
    while True:
        now = time.perf_counter()
        if opened is None and now >= t0:
            opened = program_counters(eng)
        if now >= end:
            break
        if run.trace and not traced and now >= trace_from:
            H.start_trace(run)
            traced = True
        while pending and t0 + pending[0].due_s <= now:
            off = pending.popleft()
            r = Rec(off, Request(off.idx, off.prompt, off.max_new_tokens,
                                 off.temperature, off.top_k), t0 + off.due_s)
            recs.append(r)
            r.submit = time.perf_counter()
            try:
                eng.submit(r.req)
            except ValueError as e:      # refused at the door
                r.error = repr(e)
                continue
            live[off.idx] = r
        if not eng.has_work:
            nxt = t0 + pending[0].due_s if pending else end
            time.sleep(max(0.0, min(nxt, end) - time.perf_counter()))
            continue
        ts = time.perf_counter()
        with spans("bench.step"):
            eng.step()
        te = time.perf_counter()
        steps += 1
        for s in eng.scheduler.slots:
            if s is not None:
                r = live.get(s.req.rid)
                if r is not None and r.admit is None:
                    r.admit = ts
        for rid in list(live):
            r = live[rid]
            n = len(r.req.out_tokens)
            if len(r.tokens) < n:
                if r.admit is None:        # admitted and finished in one step
                    r.admit = ts
                r.tokens.extend([te] * (n - len(r.tokens)))
            if r.req.done:
                del live[rid]
    run.records["counters"] = {k: v - opened[k]
                               for k, v in program_counters(eng).items()}
    run.records["queue_end"] = len(eng.scheduler.waiting)
    run.records["queue_start"] = sum(r.due < t0 and (
        r.admit is None or r.admit > t0) for r in recs)
    run.records["steps"] = steps
    run.records["traced"] = traced
    return recs


def _sample(recs: list[Rec], seed: int, want_tokens: int,
            most: int) -> list[Rec]:
    """Finished requests to check: the longest, then others drawn from the
    seed until ``want_tokens`` served tokens or ``most`` requests."""
    done = [r for r in recs if r.req.done
            and not r.req.expired
            and len(r.req.out_tokens) == r.off.max_new_tokens]
    if not done:
        return []
    done.sort(key=lambda r: r.off.idx)
    longest = max(done, key=lambda r: len(r.off.prompt) + r.off.max_new_tokens)
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 7])
    rest = [rest[i] for i in rng.permutation(len(rest))]
    out, n = [longest], longest.off.max_new_tokens
    for r in rest:
        if n >= want_tokens or len(out) >= most:
            break
        out.append(r)
        n += r.off.max_new_tokens
    return out


def served_gaps(family, seed: int, engine_seed: int, dims, sample: list[Rec],
                *, pad_to: int, rows_pad: int, control: bool = False) -> dict:
    """Widest gaps by which served tokens lie below the float32
    reference's choice (the ``family`` module's ``logits_at``) at their
    positions, drawn under the engine's keys
    (``engine_seed``, request id, token index): ``gap`` (compared) and
    ``strict`` (without the allowance at the top-k boundary).  With
    ``control``, also ``control``: the widest gap of the tokens that the
    float8 reference puts first at the same positions, read in the
    float32 reference."""
    seqs, rows, toks = [], [], []
    for r in sample:
        p, out = r.off.prompt, np.asarray(r.req.out_tokens, np.int32)
        seqs.append(np.concatenate([p, out[:-1]]))
        rows.append(np.arange(len(p) - 1, len(p) - 1 + len(out)))
        toks.append(out)
    kw = dict(pad_to=pad_to, rows_pad=rows_pad)
    logits = family.logits_at(seed, dims, seqs, rows, **kw)
    lower = family.logits_at(seed, dims, seqs, rows, matmul="fp8", **kw) \
        if control else [None] * len(sample)

    def widest(use_lower: bool) -> tuple[float, float]:
        got = [ref_sample.request_gaps(
            engine_seed, r.off.idx, lg, t, temperature=r.off.temperature,
            top_k=r.off.top_k, noise_dtype=dims.param_dtype,
            rows_pad=rows_pad, control_logits=lo if use_lower else None)
            for r, lg, t, lo in zip(sample, logits, toks, lower)]
        return (max(float(g.max()) for g, _ in got),
                max(float(st.max()) for _, st in got))

    out = dict(zip(("gap", "strict"), widest(False)))
    if control:
        out["control"], out["control_strict"] = widest(True)
    return out


class Served:
    """What set-up builds and the window and the check use."""

    def __init__(self, run: H.Run):
        import jax
        from repro.launch.device import CompileLog
        from repro.launch.mesh import make_host_mesh
        from repro.models import build_model
        from repro.runtime.dist import make_dist
        from repro.serve.engine import Request, ServeEngine

        cell = run.cell
        self.Request = Request
        self.family = H.family(cell)
        self.cfg = cfg = model_config(cell, run.rehearse)
        self.dims = dims_for(cell, cfg, run.rehearse)
        self.engine_cfg, self.traffic, self.rate = settings(cell, run.rehearse)
        self.lead_in_s = float(cell.spec.get(
            "rehearsal", {}).get("lead_in_s", 0.0) if run.rehearse
            else cell.spec.get("lead_in_s", 0.0))
        self.gen = H.load_module(
            cell.root / "traffic" / f"{self.traffic['generator']}.py", "traffic")
        self.clog = CompileLog()
        api = build_model(cfg)
        self.params = jax.block_until_ready(
            self.family.program_params(api, self.dims, run.seed))
        self.engine_seed = run.seed % 2**31
        self._make = lambda: ServeEngine(
            api, self.params,
            dist=make_dist(make_host_mesh(), impl=cell.spec["abi_backend"]),
            seed=self.engine_seed, **self.engine_cfg)
        self.eng = None
        self.new_engine(run.spans)

    def new_engine(self, spans: H.Spans) -> None:
        """A fresh engine (empty pages and queue), instrumented and warm."""
        if self.eng is not None:
            self.eng.decode_sync.free()
            self.eng = None
            gc.collect()
        self.eng = self._make()
        if not self.eng.paged or self.eng.decode_sync is None:
            raise H.BenchError(f"{self.cfg.name} does not serve on the paged path")
        _instrument(self.eng, spans)
        _warm(self.eng, self.Request, self.traffic, self.cfg.vocab_size)

    def offered(self, seed: int, seconds: float, rate: float | None = None):
        return self.gen.generate(self.traffic, seed=seed,
                                 rate_per_s=self.rate if rate is None else rate,
                                 seconds=seconds, lead_in_s=self.lead_in_s,
                                 vocab_size=self.cfg.vocab_size)

    def free(self) -> None:
        self.eng.decode_sync.free()
        del self.eng, self.params
        gc.collect()


def window_facts(run: H.Run, recs, rate: float) -> dict:
    t0 = run.window[0]
    lateness = [r.submit - r.due for r in recs]
    due = [r for r in recs if r.due >= t0]
    return {"phase": "window", "cell": run.cell.name, "seed": run.seed,
            "rate_per_s": rate,
            "lead_in_s": t0 - run.records["lead_in_start"],
            "lead_in": len(recs) - len(due),
            "due": len(due), "finished": sum(r.req.done for r in recs),
            "queue_start": run.records["queue_start"],
            "queue_end": run.records["queue_end"],
            "generator_late_max_s": max(lateness, default=0.0),
            "generator_late_p95_s": _p(lateness, 95),
            "engine_steps": run.records["steps"],
            "counters": run.records["counters"]}


def limit_for(run: H.Run) -> float:
    """The cell's ``max_logit_gap`` limit (its ``rehearsal.check`` one when
    rehearsing, where it has one).  A limit not yet set from chip readings
    stops the run before set-up, unless the run is calibrating, that is
    reading the numbers a limit is set from."""
    check = dict(run.cell.spec["check"])
    if run.rehearse:
        check.update(run.cell.spec.get("rehearsal", {}).get("check", {}))
    if check.get("max_logit_gap") is None:
        if run.extra.get("calibrating"):
            return math.inf
        raise H.BenchError(f"{run.cell.name}: no limit set for max_logit_gap")
    return float(check["max_logit_gap"])


def run(run: H.Run) -> None:
    import jax
    cell = run.cell
    limit = limit_for(run)
    sv = Served(run)
    offered = sv.offered(run.seed, run.seconds)
    run.counters["compiles_setup"] = sv.clog.count

    c0 = sv.clog.count
    recs = _window(run, sv.eng, sv.Request, offered, sv.lead_in_s)
    run.setup_s = run.records["lead_in_start"] - run.t_start
    run.counters["compiles_window"] = sv.clog.count - c0
    devs = jax.devices()[:cell.chips]
    stats = [d.memory_stats() or {} for d in devs]
    run.device.update({
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0) for s in stats)})
    if run.trace and run.records["traced"]:
        H.stop_trace(run, lambda: _mark_programs(sv.eng, run.spans))
    run.records["requests"] = recs
    due = [r for r in recs if r.due >= run.window[0]]
    run.attempted = len(due)
    run.failed = sum(r.error is not None or r.req.expired for r in due)
    print(json.dumps({**window_facts(run, recs, sv.rate),
                      "family": sv.family.__name__,
                      "compiles_setup": run.counters["compiles_setup"],
                      "compiles_window": run.counters["compiles_window"],
                      "setup_s": run.setup_s}), flush=True)

    check = cell.spec["check"]
    sample = _sample(recs, run.seed, check["sample_tokens"],
                     check["sample_requests"])
    sv.free()
    if not sample:
        run.checks["max_logit_gap"] = (math.inf, limit)
        return
    tr = time.perf_counter()
    control = bool(run.extra.get("control"))
    got = served_gaps(sv.family, run.seed, sv.engine_seed, sv.dims, sample,
                      pad_to=sv.engine_cfg["max_seq"],
                      rows_pad=int(sv.traffic["output_len"]["max"]),
                      control=control)
    run.checks["max_logit_gap"] = (got["gap"], limit)
    fact = {"phase": "check", "sampled": len(sample),
            "sampled_tokens": sum(len(r.req.out_tokens) for r in sample),
            "reference_s": time.perf_counter() - tr,
            "strict_gap": got["strict"]}
    if control:
        fact.update(control_gap=got["control"],
                    control_strict_gap=got["control_strict"])
        run.extra["control_gaps"] = {"max_logit_gap": got["control"]}
    run.extra["program_gaps"] = {"max_logit_gap": got["gap"]}
    run.extra["strict_gaps"] = {"max_logit_gap": got["strict"]}
    print(json.dumps(fact), flush=True)


def _p(xs, q):
    return float(np.percentile(xs, q)) if xs else 0.0
