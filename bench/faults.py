"""Faults planted under the timed path, for the tests and for reading a
fault's numbers on the chip (``bench/calibrate.py --plant <name>``).

Each function patches the program in this process before the cell is
built; a cell's check has to come out not correct under each fault the
cell can have.
"""
from __future__ import annotations


def state_unchanged():
    """Training: the step returns the state it was given."""
    from repro.train import train_loop
    make = train_loop.make_train_step

    def patched(*a, **kw):
        good = make(*a, **kw)

        def step(state, batch):
            _, metrics = good(state, batch)
            return state, metrics
        return step
    train_loop.make_train_step = patched


def half_batch():
    """Training: half of each rank's batch is left out, the mean taken
    over the rest."""
    from repro.train import train_loop
    grads = train_loop._microbatched_grads

    def patched(loss_fn, params, batch, n_micro):
        import jax
        half = jax.tree.map(lambda x: x[:max(1, x.shape[0] // 2)], batch)
        return grads(loss_fn, params, half, n_micro)
    train_loop._microbatched_grads = patched


def exchange_left_out():
    """Training: the ZeRO-1 reduce-scatter is skipped; each rank updates
    its shard from its own gradient alone."""
    import jax
    from repro.core.communicator import comm_rank_traced
    from repro.train import grad_sync, train_loop

    def start(dist, flat_g, **kw):
        n = flat_g.shape[0] // dist.dp_size
        r = comm_rank_traced(dist.abi.comms.info(dist.dp_comm))
        own = jax.lax.dynamic_slice_in_dim(flat_g, r * n, n)
        return grad_sync.PendingShard(dist.abi, "value", own * dist.dp_size,
                                      dist.dp_size), None
    train_loop.reduce_scatter_grads_start = start


def altered_token():
    """Serving: every sampled token is replaced by its neighbour id."""
    from repro.serve.engine import ServeEngine
    good = ServeEngine._sample_one

    def bad(self, row_logits, req):
        return (good(self, row_logits, req) + 1) % self.cfg.vocab_size
    ServeEngine._sample_one = bad


def top_k_ignored():
    """Serving: sampled tokens are drawn from the whole vocabulary, the
    request's ``top_k`` left out."""
    from repro.serve import engine

    def bad(self, row_logits, req):
        if req.temperature <= 0.0:
            return int(row_logits.argmax())
        key = self._req_key(req.rid, len(req.out_tokens))
        return int(engine.sample(engine.jnp.asarray(row_logits), key,
                                 float(req.temperature), 0))
    engine.ServeEngine._sample_one = bad


def sample_key_wrong():
    """Serving: each token is drawn under the key of the request's next
    token."""
    from repro.serve.engine import ServeEngine
    good = ServeEngine._req_key

    def bad(self, rid, step):
        return good(self, rid, step + 1)
    ServeEngine._req_key = bad


def decode_state_unchanged():
    """Serving: the decode step's KV writes are dropped."""
    from repro.models import transformer
    good = transformer.decode_step_paged

    def bad(params, token, pages, *a, **kw):
        logits, _ = good(params, token, pages, *a, **kw)
        return logits, pages
    transformer.decode_step_paged = bad


FAULTS = {"state-unchanged": state_unchanged, "half-batch": half_batch,
          "exchange-left-out": exchange_left_out,
          "token-altered": altered_token,
          "top-k-ignored": top_k_ignored,
          "sample-key-wrong": sample_key_wrong,
          "decode-state-unchanged": decode_state_unchanged}
