"""Serving engine: continuous batching over a paged KV cache, with decode
collectives driven by ONE persistent plan group per token step.

Architecture (dense/moe families):

* **paged KV** — one preallocated block slab
  (:func:`~repro.models.transformer.init_paged_cache`), blocks owned per
  request through :class:`~.kv_cache.BlockAllocator` handles; decode
  attention reads through per-request block tables
  (:func:`~repro.models.transformer.decode_step_paged`).
* **continuous batching** — :class:`~.scheduler.Scheduler` admits/evicts
  at step granularity; each engine step runs at most one B=1 prefill
  *chunk* (long prompts never stall running decodes) plus one full-width
  decode step.
* **fixed decode shape** — decode always runs the full ``max_batch``
  batch; inactive slots carry token 0, length 0, and an all-null block
  table (their garbage writes land in the reserved null block).  Because
  the compiled decode function and each row's float math are batch-
  composition-independent, continuous-batched output is **token-identical
  to the one-request-at-a-time oracle** — the contract
  ``tests/test_serve_engine.py`` pins.
* **per-request RNG** — sampling keys are
  ``fold_in(fold_in(PRNGKey(seed), rid), step)``; a request's sampled
  tokens never depend on which other requests share its batch (the old
  engine-wide ``split`` chain did — that was the PR-8 bugfix).
* **sampling on the device** — each sampled row is one call of a compiled
  row program (``jit_sample_row``) on a row of the logits that stays on
  the device, dispatched before anything waits, so the row programs queue
  behind the decode program; only the ``(max_batch,)`` tokens reach the
  host, stacked on the device into one array and copied once.
* **decode plan group** — per-token tensor-parallel control-plane sync
  (sampled tokens + active mask broadcast from tp root 0, the
  sample-on-rank-0 idiom) is built ONCE at engine init as two persistent
  ``bcast_init`` plans fused into one ``plan_group("decode-tp")``; every
  token step is a single ``group.start()/wait()`` pair — no per-token ABI
  work, and a ``CallCounter`` attached via ``attach_tool`` counts exactly
  one ``decode-tp`` call per sampling step.

ssm/hybrid families keep the legacy static-batch path (no KV pages to
page).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime import spans
from .kv_cache import BlockAllocator
from .scheduler import DECODE, Scheduler


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray           # (S,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0
    top_k: int = 0
    out_tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    # -- robustness bookkeeping (PR 9) ------------------------------------
    #: engine steps from submission before the request is abandoned
    #: (None: no deadline); measured against ``stats["steps"]``
    deadline_steps: Optional[int] = None
    submit_step: Optional[int] = None  # stamped by ServeEngine.submit
    retries: int = 0                   # replay count (supervisor recovery)
    expired: bool = False              # deadline passed; done, no more tokens
    failed: bool = False               # dropped after max_retries replays
    # -- time stamps on time.perf_counter ----------------------------------
    t_submit: Optional[float] = None   # accepted by ServeEngine.submit
    t_admit: Optional[float] = None    # first admitted to a slot
    t_first: Optional[float] = None    # first token appended


def greedy(logits):
    """The greedy branch of :func:`sample`: the first largest logit."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def draw(logits, key, temperature, top_k: int):
    """The sampled branch of :func:`sample`, shared with the engine's
    compiled row program.  ``temperature`` (a float, or a traced scalar) is
    cast to the logits' dtype before the divide, which is what dividing by
    a Python float does; the Gumbel noise is drawn in the logits' dtype and
    the top-k is exact."""
    logits = logits / jnp.asarray(temperature).astype(logits.dtype)
    if top_k > 0:
        vals, _ = jax.lax.top_k(logits, top_k)
        logits = jnp.where(logits < vals[..., -1:], -1e30, logits)
    return jax.random.categorical(key, logits).astype(jnp.int32)


def sample(logits, key, temperature: float, top_k: int):
    if temperature <= 0.0:
        return greedy(logits)
    return draw(logits, key, temperature, top_k)


class DecodeSync:
    """The per-token decode collective, persistent-plan-group edition.

    Sampling happens on the tensor-parallel root; the sampled token vector
    and the active-slot mask are broadcast to the other tp ranks so every
    rank feeds identical tokens into the next decode step (at tp=1 the
    broadcast is the identity, but the plan group still runs — which is
    what lets a 1-device test count it).  Both broadcasts are built ONCE as
    persistent plans and fused into one ``plan_group`` named
    ``"decode-tp"``; :meth:`step` is a single ``start()/wait()`` pair.

    :meth:`step_pooled` runs the same two broadcasts through the pooled
    nonblocking ``ibcast``/``waitall`` path — the bitwise reference the
    multidev battery compares the group against.
    """

    NAME = "decode-tp"

    def __init__(self, abi, comm, max_batch: int, mesh, *,
                 wait_timeout_s: Optional[float] = None) -> None:
        from jax.sharding import PartitionSpec as P

        from ..core.compat import host_shard_map

        self.abi = abi
        self.comm = comm
        self.mesh = mesh     # kept for supervisor rebuilds on a survivor comm
        # deadline for the group/pooled waits: None blocks forever (the
        # faithful hang on a dropped broadcast); a bound turns the drop into
        # PAX_ERR_TIMEOUT, which the supervisor retries and escalates.  Read
        # per call — the region below re-runs its Python on every call, so a
        # live change applies to the very next token step.
        self.wait_timeout_s = wait_timeout_s
        ex = jax.ShapeDtypeStruct((max_batch,), jnp.int32)
        self._p_tok = abi.bcast_init(ex, 0, comm)
        self._p_act = abi.bcast_init(ex, 0, comm)
        self.group = abi.plan_group([self._p_tok, self._p_act],
                                    name=self.NAME)

        # the collectives bind mesh axis names, so the start/wait pair runs
        # in a host-called shard_map region (payloads replicated): each call
        # re-drives the plan protocol and the tool interposition — one
        # before/after per token step, which is what the counting test pins
        # — and after the first step reuses its compiled program
        def _group_call(tok, act):
            outs = abi.wait(self.group.start([tok, act]),
                            timeout_s=self.wait_timeout_s)
            return outs[0], outs[1]

        def _pooled_call(tok, act):
            outs = abi.waitall([abi.ibcast(tok, 0, comm),
                                abi.ibcast(act, 0, comm)],
                               timeout_s=self.wait_timeout_s)
            return outs[0], outs[1]

        spec = (P(), P())
        self._group_call = host_shard_map(_group_call, mesh=mesh,
                                          in_specs=spec, out_specs=spec)
        self._pooled_call = host_shard_map(_pooled_call, mesh=mesh,
                                           in_specs=spec, out_specs=spec)

    @property
    def calls(self) -> int:
        """Regions run, group and pooled paths together."""
        return self._group_call.calls + self._pooled_call.calls

    @property
    def compiles(self) -> int:
        """Region programs compiled, group and pooled paths together."""
        return self._group_call.compiles + self._pooled_call.compiles

    def reset(self) -> None:
        """Abort a start whose wait timed out (the post-timeout contract):
        force the group and member plans inactive so the next token step
        starts on a clean slot instead of a wedged one."""
        self.group.reset()
        self._p_tok.reset()
        self._p_act.reset()

    def step(self, tokens: np.ndarray, active: np.ndarray):
        """ONE group start/wait for the whole token step."""
        with spans.span(spans.SERVE_SYNC):
            tok, act = self._group_call(jnp.asarray(tokens),
                                        jnp.asarray(active))
            tok, act = np.asarray(tok), np.asarray(act)
            # corruption folded into the wire payload in-trace surfaces
            # here, at materialization (no-op when integrity mode is off)
            self.abi.verify_clean((tok, act), "decode-tp sync")
        return tok, act

    def step_pooled(self, tokens: np.ndarray, active: np.ndarray):
        """The pooled ``i*`` reference path (two requests, one waitall)."""
        tok, act = self._pooled_call(jnp.asarray(tokens), jnp.asarray(active))
        tok, act = np.asarray(tok), np.asarray(act)
        self.abi.verify_clean((tok, act), "decode-tp pooled sync")
        return tok, act

    def free(self) -> None:
        self.group.free()
        self._p_tok.free()
        self._p_act.free()


class ServeEngine:
    """Continuous-batching engine over ``max_batch`` decode slots."""

    def __init__(self, api, params, *, max_batch: int = 4, max_seq: int = 512,
                 dist=None, eos_id: Optional[int] = None,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 prefill_chunk: int = 32, seed: int = 0) -> None:
        self.api = api
        self.cfg = api.cfg
        self.params = params
        self.dist = dist
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.eos_id = eos_id
        self.seed = seed
        self._base_key = jax.random.PRNGKey(seed)
        # prefill_positions counts chunk positions computed (pads too),
        # decode_rows the decoding rows summed over decode steps,
        # sampled_rows the rows drawn by the row program (greedy ones not)
        self.stats = {"prefill_tokens": 0, "prefill_positions": 0,
                      "decode_steps": 0, "decode_rows": 0,
                      "prefill_chunks": 0, "requests": 0, "steps": 0,
                      "expired": 0, "sampled_rows": 0}
        self.last_expired: list = []   # requests expired by the last step()
        self.paged = self.cfg.family in ("dense", "moe")
        self.decode_sync: Optional[DecodeSync] = None
        spans.install_gc_span()

        if self.paged:
            from ..models import transformer
            width = -(-max_seq // block_size)
            if num_blocks is None:
                num_blocks = max_batch * width + 1   # +1: reserved null block
            self.block_size = block_size
            self.prefill_chunk = prefill_chunk
            self.alloc = BlockAllocator(num_blocks, block_size)
            self.scheduler = Scheduler(self.alloc, max_batch=max_batch,
                                       prefill_chunk=prefill_chunk,
                                       table_width=width)
            self._pages = transformer.init_paged_cache(
                self.cfg, num_blocks, block_size)
            # the two compiled steps of the serving loop, shapes frozen:
            # prefill (1, chunk), decode (max_batch, 1); pages donated so
            # the slab updates in place on device.  Named functions, so
            # the programs are jit_prefill_chunk_paged and
            # jit_decode_step_paged in a profile
            def prefill_chunk_paged(p, toks, pages, table, start):
                return transformer.prefill_chunk_paged(
                    p, toks, pages, table, start, self.cfg, dist)

            def decode_step_paged(p, tok, pages, tables, lengths):
                return transformer.decode_step_paged(
                    p, tok, pages, tables, lengths, self.cfg, dist)

            self._prefill_chunk_fn = jax.jit(prefill_chunk_paged,
                                             donate_argnums=(2,))
            self._decode_paged = jax.jit(decode_step_paged,
                                         donate_argnums=(2,))
            if dist is not None:
                self.decode_sync = DecodeSync(dist.abi, dist.tp_comm,
                                              max_batch, dist.mesh)
        else:
            self._decode = jax.jit(
                lambda p, tok, cache, idx: api.decode_step(
                    p, tok, cache, idx, dist))

        # the row program: one sampled token from one row of logits left on
        # the device.  Built per engine and keyed inside the trace through
        # self._req_key, so a replaced _req_key is traced in; the
        # temperature is traced (one compile per row width, dtype and
        # top_k), and the program is jit_sample_row in a profile.  The
        # base key is an argument, bound to self._base_key while tracing:
        # as a constant it would make a new program, and a compile of the
        # exact top-k (tens of seconds on a TPU), for every engine seed
        self._sample_traces = 0

        def sample_row(row, base_key, rid, step, temperature, top_k):
            self._sample_traces += 1
            own, self._base_key = self._base_key, base_key
            try:
                key = self._req_key(rid, step)
            finally:
                self._base_key = own
            return draw(row, key, temperature, top_k)

        self._sample_row = jax.jit(sample_row, static_argnames=("top_k",))

        # a decode step's tokens, one per slot, as one (max_batch,) array:
        # one copy to the host instead of one a row.  Idle slots hold 0
        def stack_tokens(tokens):
            return jnp.stack(tokens)

        self._stack_tokens = jax.jit(stack_tokens)
        self._no_token = jnp.zeros((), jnp.int32)

    # -- per-request RNG (batch-composition-independent) --------------------
    def _req_key(self, rid: int, step: int):
        """Key for request ``rid``'s ``step``-th sampled token: depends on
        (engine seed, rid, step) ONLY — never on batch composition."""
        return jax.random.fold_in(
            jax.random.fold_in(self._base_key, rid), step)

    @property
    def sample_compiles(self) -> int:
        """Traces of the row program (one per row width, dtype and
        ``top_k``)."""
        return self._sample_traces

    def _sample_one(self, row_logits: jax.Array, req: Request) -> jax.Array:
        """``req``'s next token from its row of logits on the device, as a
        0-d device array (not waited for)."""
        if req.temperature <= 0.0:
            return greedy(row_logits)
        self.stats["sampled_rows"] += 1
        # uint32, as fold_in reads them; fixed dtypes keep one signature
        return self._sample_row(row_logits, self._base_key,
                                np.uint32(req.rid),
                                np.uint32(len(req.out_tokens)),
                                np.float32(req.temperature),
                                top_k=int(req.top_k))

    def _append(self, req: Request, tok: int) -> None:
        req.out_tokens.append(tok)
        if req.t_first is None:
            req.t_first = time.perf_counter()
        if self.eos_id is not None and tok == self.eos_id:
            req.done = True
        if len(req.out_tokens) >= req.max_new_tokens:
            req.done = True

    # -- public API ---------------------------------------------------------
    def submit(self, req: Request) -> None:
        """Queue a request (admitted by the next :meth:`step` with a free
        slot and enough KV blocks)."""
        if not self.paged:
            raise NotImplementedError(
                f"submit/step serving requires a paged family, not "
                f"{self.cfg.family}; use run()")
        if len(req.prompt) == 0:
            raise ValueError(f"request {req.rid}: empty prompt")
        if req.submit_step is None:
            req.submit_step = self.stats["steps"]  # deadline clock starts now
        self.scheduler.submit(req)
        req.t_submit = time.perf_counter()
        self.stats["requests"] += 1

    def rebuild_decode_sync(self, abi, comm, mesh,
                            wait_timeout_s: Optional[float] = None) -> None:
        """Bind a fresh ``DecodeSync`` (new plans + plan group) on ``comm``
        — the supervisor's recovery hook after a tp-comm shrink.  The old
        sync must already be retired (``free()``)."""
        self.decode_sync = DecodeSync(abi, comm, self.max_batch, mesh,
                                      wait_timeout_s=wait_timeout_s)

    @property
    def has_work(self) -> bool:
        return self.paged and self.scheduler.has_work

    def generate(self, prompt: np.ndarray, *, max_new_tokens: int = 32,
                 temperature: float = 0.0, top_k: int = 0) -> np.ndarray:
        reqs = [Request(0, prompt, max_new_tokens, temperature, top_k)]
        self.run(reqs)
        return np.asarray(reqs[0].out_tokens, np.int32)

    def run(self, requests: list[Request]) -> None:
        """Serve a closed batch to completion (continuous-batched on the
        paged path; legacy static batching for ssm/hybrid)."""
        if self.paged:
            for r in requests:
                self.submit(r)
            self.drain()
        else:
            self.stats["requests"] += len(requests)
            self._run_static(requests)

    def drain(self) -> None:
        """Step until the queue and every slot are empty."""
        while self.has_work:
            self.step()

    # -- the engine step -----------------------------------------------------
    def step(self) -> None:
        """One serving step: admit waiting requests into free slots, run at
        most one prefill chunk, then one decode step for every decoding
        slot (ending in one ``decode-tp`` plan-group start/wait)."""
        with spans.span(spans.SERVE_STEP):
            sched = self.scheduler
            self.stats["steps"] += 1
            with spans.span(spans.SERVE_ADMIT):
                # deadline pass first: an expired request frees its blocks
                # before admission, so its capacity funds the queue head
                # this very step
                self.last_expired = sched.expire(self.stats["steps"])
                self.stats["expired"] += len(self.last_expired)
                sched.admit()
            i = sched.prefill_slot()
            if i is not None:
                self._prefill_step(i)
            dslots = sched.decode_slots()
            if dslots:
                self._decode_step(dslots)

    def _prefill_step(self, i: int) -> None:
        """Feed the next B=1 prompt chunk of slot ``i`` into its KV blocks;
        on the final chunk, sample the request's first token."""
        with spans.span(spans.SERVE_PREFILL) as sp:
            seq = self.scheduler.slots[i]
            req, C = seq.req, self.prefill_chunk
            start = seq.fed
            real = np.asarray(req.prompt[start:start + C], np.int32)
            if sp.is_enabled():
                sp.set_metadata(start=start, real=len(real), chunk=C)
            chunk = np.zeros((1, C), np.int32)
            chunk[0, :len(real)] = real
            logits, self._pages = self._prefill_chunk_fn(
                self.params, jnp.asarray(chunk), self._pages,
                jnp.asarray(seq.table[None]), jnp.int32(start))
            seq.fed = start + C
            self.stats["prefill_tokens"] += int(len(real))
            self.stats["prefill_positions"] += C
            self.stats["prefill_chunks"] += 1
            if seq.prefill_done:
                last = (seq.prompt_len - 1) - start  # last real row of chunk
                tok = int(self._sample_one(logits[0, last], req))
                self._append(req, tok)
                if req.done:
                    self.scheduler.finish(i)
                else:
                    seq.state = DECODE

    def _decode_step(self, dslots: list[int]) -> None:
        """One full-width decode step.  Inactive slots run too (fixed
        shape), but with length 0 and an all-null block table: their writes
        land in the reserved null block and their logits are discarded."""
        with spans.span(spans.SERVE_DECODE) as sp:
            sched = self.scheduler
            B = self.max_batch
            with spans.span(spans.SERVE_DECODE_DISPATCH):
                toks = np.zeros((B, 1), np.int32)
                lengths = np.zeros((B,), np.int32)
                # inactive rows keep NULL_BLOCK tables
                tables = np.zeros((B, sched.table_width), np.int32)
                for i in dslots:
                    seq = sched.slots[i]
                    toks[i, 0] = seq.req.out_tokens[-1]
                    lengths[i] = seq.prompt_len + len(seq.req.out_tokens) - 1
                    tables[i] = seq.table
                logits, self._pages = self._decode_paged(
                    self.params, jnp.asarray(toks), self._pages,
                    jnp.asarray(tables), jnp.asarray(lengths))
            if sp.is_enabled():
                sp.set_metadata(rows=len(dslots),
                                context=int(lengths.sum()) + len(dslots))
            self.stats["decode_steps"] += 1
            self.stats["decode_rows"] += len(dslots)
            # the row programs queue behind the decode program on the
            # device; only the (max_batch,) tokens come to the host.  The
            # rows come from one split program (list(logits)): indexing
            # logits[i] would dispatch several eager ops a row
            with spans.span(spans.SERVE_SAMPLE):
                rows = list(logits)
                drawn = [self._no_token] * B
                for i in dslots:
                    tok = self._sample_one(rows[i], sched.slots[i].req)
                    # a replaced sampler may hand back a Python int
                    drawn[i] = (tok if isinstance(tok, jax.Array)
                                else np.int32(tok))
                drawn = self._stack_tokens(drawn)
            with spans.span(spans.SERVE_DECODE_WAIT):
                drawn.block_until_ready()
            with spans.span(spans.SERVE_DECODE_COPY):
                sampled = np.asarray(drawn)
            active = np.zeros((B,), np.int32)
            active[dslots] = 1
            if self.decode_sync is not None:
                sampled, active = self.decode_sync.step(sampled, active)
            for i in dslots:
                seq = sched.slots[i]
                self._append(seq.req, int(sampled[i]))
                if seq.req.done:
                    sched.finish(i)

    # -- legacy static batching (ssm/hybrid: no KV pages) --------------------
    def _run_static(self, requests: list[Request]) -> None:
        """Pad all prompts to one length, prefill together, decode
        round-robin until every request finishes (the pre-PR-8 path, kept
        for the recurrent families)."""
        B = len(requests)
        S = max(len(r.prompt) for r in requests)
        tokens = np.zeros((B, S), np.int32)
        for i, r in enumerate(requests):
            tokens[i, S - len(r.prompt):] = r.prompt  # left-pad
        tokens = jnp.asarray(tokens)

        state = self.api.decode_init(B, self.max_seq)
        logits = None
        for t in range(S):
            logits, state = self._decode(self.params, tokens[:, t:t + 1],
                                         state, jnp.int32(t))
        idx = jnp.int32(S)
        self.stats["prefill_tokens"] += int(B * S)

        max_new = max(r.max_new_tokens for r in requests)
        cur = self._sample_rows(logits, requests)
        self._append_live(cur, requests)
        for _ in range(1, max_new):
            if all(r.done for r in requests):
                break
            logits, state = self._decode(self.params,
                                         jnp.asarray(cur)[:, None], state, idx)
            idx = idx + 1
            self.stats["decode_steps"] += 1
            cur = self._sample_rows(logits, requests)
            self._append_live(cur, requests)

    def _sample_rows(self, logits, requests: list[Request]) -> np.ndarray:
        drawn = [self._sample_one(row, r)
                 for row, r in zip(list(logits), requests)]
        return np.asarray(jax.device_get(drawn), np.int32)

    def _append_live(self, cur, requests: list[Request]) -> None:
        for i, r in enumerate(requests):
            if not r.done:
                self._append(r, int(cur[i]))
