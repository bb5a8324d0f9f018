"""Training cells: the jitted, donated ZeRO-1 train step through the ABI.

Set-up builds one object, the compiled step with its state (weights from
the seed, the ZeRO-1 flat optimizer state sharded over the data-parallel
devices, the plan groups of ``init_state``), and drives it through its
first three steps on rows that all differ, reading what the check needs:
each step's loss, the first step's gradient norm, the first gradient as
the optimizer got it (from its moments after one step, per leaf), and each
leaf's change over the three steps.  The window runs the same object on,
one step after another, for ``--seconds``.  Once it has closed and the
program's state is freed, the float32 reference runs the same three steps
and the worst leaf's gap is compared with the cell's limits.
"""
from __future__ import annotations

import gc
import json
import math
import time

import numpy as np

from .. import harness as H
from ._model import dims_for, model_config

TRACE_SECONDS = 8.0
CHECKED_STEPS = 3

class Trained:
    def __init__(self, run: H.Run):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.device import CompileLog
        from repro.launch.mesh import make_host_mesh
        from repro.models import build_model
        from repro.optim.adamw import AdamWConfig
        from repro.runtime.dist import make_dist
        from repro.train import train_loop

        cell = run.cell
        self.family = H.family(cell)
        self.cfg = cfg = model_config(cell, run.rehearse)
        self.dims = dims_for(cell, cfg, run.rehearse)
        t = dict(cell.spec["trainer"])
        if run.rehearse:
            t.update(cell.spec.get("rehearsal", {}).get("trainer", {}))
        self.global_batch, self.seq_len = t["global_batch"], t["seq_len"]
        self.adam = self.family.Adam(lr=float(t["lr"]))
        self.gen = H.load_module(
            cell.root / "traffic" / f"{cell.traffic['generator']}.py", "traffic")
        self.traffic, self.seed = cell.traffic, run.seed
        self.clog = CompileLog()
        api = build_model(cfg)
        self.dist = make_dist(make_host_mesh(), impl=cell.spec["abi_backend"])
        if self.dist.dp_size != cell.chips:
            raise H.BenchError(f"dp={self.dist.dp_size}, the cell asks for "
                               f"{cell.chips} chips")
        state = train_loop.init_state(api, jax.random.PRNGKey(0), self.dist)
        rep = NamedSharding(self.dist.mesh, P())
        params = jax.device_put(
            self.family.program_params(api, self.dims, run.seed), rep)
        self.state = state._replace(params=params)
        a = self.adam
        self.step = jax.jit(train_loop.make_train_step(
            api, self.dist, AdamWConfig(lr=a.lr, b1=a.b1, b2=a.b2, eps=a.eps,
                                        weight_decay=a.weight_decay,
                                        grad_clip=a.grad_clip)),
            donate_argnums=(0,))
        paths = jax.tree_util.tree_flatten_with_path(params)[0]
        self.leaves = [(self.family.leaf_name(p), int(x.size))
                       for p, x in paths]

    def batch(self, k: int, host: bool = False):
        import jax.numpy as jnp
        b = self.gen.batch(self.traffic, seed=self.seed, step=k,
                           global_batch=self.global_batch, seq_len=self.seq_len,
                           vocab_size=self.cfg.vocab_size)
        return b if host else {n: jnp.asarray(v) for n, v in b.items()}

    def _flat_leaf_norms(self, flat, scale: float) -> dict:
        import jax
        import jax.numpy as jnp

        def norms(v):
            out, o = [], 0
            for _, n in self.leaves:
                out.append(jnp.sqrt(jnp.sum(jnp.square(v[o:o + n]))))
                o += n
            return out
        vals = jax.jit(norms)(flat)
        return {name: float(x) * scale for (name, _), x in zip(self.leaves, vals)}

    def first_steps(self) -> dict:
        """Steps 1..3 through the window's own call and feed, with the
        readings the check compares."""
        import jax
        import jax.numpy as jnp
        p0 = jax.tree.map(jnp.copy, self.state.params)
        out = {"losses": []}
        for k in range(1, CHECKED_STEPS + 1):
            self.state, m = self.step(self.state, self.batch(k))
            out["losses"].append(float(m.loss))
            if k == 1:
                out["grad_norm"] = float(m.grad_norm)
                # m after one step is (1 - b1) * the clipped gradient
                out["grad_leaves"] = self._flat_leaf_norms(
                    self.state.opt.m, 1.0 / (1.0 - self.adam.b1))
        change = jax.jit(lambda p, q: jax.tree.map(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
                a.astype(jnp.float32) - b.astype(jnp.float32)))), p, q))(
            self.state.params, p0)
        flat = jax.tree_util.tree_flatten_with_path(change)[0]
        out["change_leaves"] = {self.family.leaf_name(p): float(x)
                                for p, x in flat}
        return out

    def free(self) -> None:
        self.dist.drop_zero1_plans()
        del self.state, self.step
        gc.collect()


def compare(prog: dict, want: dict) -> dict:
    """The compared numbers: each ``(value, detail)``."""
    loss = max(abs(a - b) for a, b in zip(prog["losses"], want["losses"]))
    gnorm = abs(prog["grad_norm"] - want["grad_norm"]) / want["grad_norm"]

    def worst(p, r, keep):
        med = float(np.median([r[k] for k in keep]))
        gaps = {k: abs(p[k] - r[k]) / max(r[k], med) for k in keep}
        k = max(gaps, key=gaps.get)
        return gaps[k], k

    names = list(want["grad_leaves"])
    g_med = float(np.median(list(want["grad_leaves"].values())))
    # a leaf whose reference gradient is nought to rounding (a key bias
    # under softmax) moves by round-off alone: out of the change
    moved = [k for k in names if want["grad_leaves"][k] >= 1e-3 * g_med]
    grad, grad_leaf = worst(prog["grad_leaves"], want["grad_leaves"], names)
    change, change_leaf = worst(prog["change_leaves"], want["change_leaves"], moved)
    return {"loss_gap": (loss, None), "grad_norm_gap": (gnorm, None),
            "grad_leaf_gap": (grad, grad_leaf),
            "change_leaf_gap": (change, change_leaf),
            "left_out": sorted(set(names) - set(moved))}


def _window(run: H.Run, tr: Trained) -> list:
    """Whole steps, one after another, until ``--seconds`` have passed;
    a step that ends after the close is not counted."""
    steps = []
    t0 = time.perf_counter()
    end = t0 + run.seconds
    run.window = (t0, end)
    trace_from = end - min(TRACE_SECONDS, run.seconds)
    k = CHECKED_STEPS + 1
    traced = False
    while time.perf_counter() < end:
        if run.trace and not traced and time.perf_counter() >= trace_from:
            H.start_trace(run)
            traced = True
        with run.spans("bench.step"):
            tr.state, m = tr.step(tr.state, tr.batch(k))
            loss = float(m.loss)
        t = time.perf_counter()
        if t > end:
            break
        steps.append((k, t, loss))
        k += 1
    run.records["traced"] = traced
    return steps


CHECKS = ("loss_gap", "grad_norm_gap", "grad_leaf_gap", "change_leaf_gap")


def limits_for(run: H.Run) -> dict:
    """The cell's limits (its ``rehearsal.check`` when rehearsing).  A
    limit not yet set from chip readings stops the run before set-up,
    unless the run is calibrating, that is reading the numbers a limit is
    set from."""
    check = (run.cell.spec.get("rehearsal", {}).get("check") if run.rehearse
             else run.cell.spec["check"]) or {}
    unset = [k for k in CHECKS if check.get(k) is None]
    if unset and not run.extra.get("calibrating"):
        raise H.BenchError(f"{run.cell.name}: no limit set for {unset}")
    return {k: math.inf if check.get(k) is None else float(check[k])
            for k in CHECKS}


def run(run: H.Run) -> None:
    import jax
    cell = run.cell
    limits = limits_for(run)
    tr = Trained(run)
    first = tr.first_steps()
    run.counters["compiles_setup"] = tr.clog.count
    run.setup_s = time.perf_counter() - run.t_start
    c0 = tr.clog.count
    steps = _window(run, tr)
    run.counters["compiles_window"] = tr.clog.count - c0
    devs = jax.devices()[:cell.chips]
    stats = [d.memory_stats() or {} for d in devs]
    run.device.update({
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0) for s in stats)})
    if run.trace and run.records["traced"]:
        H.stop_trace(run)
    run.records.update(steps=steps, tokens_per_step=tr.global_batch * tr.seq_len,
                       seq_len=tr.seq_len, dims=tr.dims)
    run.attempted = len(steps)
    run.failed = sum(not math.isfinite(loss) for _, _, loss in steps)
    print(json.dumps({"phase": "window", "cell": cell.name, "seed": run.seed,
                      "steps": len(steps),
                      "losses": [s[2] for s in steps[:3] + steps[-2:]],
                      "compiles_setup": run.counters["compiles_setup"],
                      "compiles_window": run.counters["compiles_window"],
                      "setup_s": run.setup_s, **first}), flush=True)

    batches = [tr.batch(k, host=True) for k in range(1, CHECKED_STEPS + 1)]
    fam, dims, adam = tr.family, tr.dims, tr.adam
    tr.free()
    t = time.perf_counter()
    want = fam.train(run.seed, dims, batches, adam, devs)
    got = compare(first, want)
    for k in CHECKS:
        run.checks[k] = (got[k][0], limits[k])
    fact = {"phase": "check", "reference_s": time.perf_counter() - t,
            "reference": {k: want[k] for k in ("losses", "grad_norm")},
            "worst": {k: got[k] for k in ("grad_leaf_gap", "change_leaf_gap")},
            "left_out_of_change": got["left_out"]}
    if run.extra.get("control"):
        ctrl = compare(fam.train(run.seed, dims, batches, adam, devs,
                                 matmul="fp8"), want)
        run.extra["control_gaps"] = {k: ctrl[k][0] for k in run.checks}
        fact["control"] = run.extra["control_gaps"]
    run.extra["program_gaps"] = {k: v for k, (v, _) in run.checks.items()}
    print(json.dumps(fact), flush=True)
