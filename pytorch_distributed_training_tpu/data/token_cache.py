"""Device-cached token stream: the whole LM corpus resident in HBM, with
on-device window sampling and multi-step training scans.

The image twin (``data/device_cache.py``) exists because the reference's
per-step host->device feed (/root/reference/src/main.py:69-70) is the wrong
shape for TPU; the LM case is even more extreme: a 100M-token corpus is only
~200 MB as uint16 — smaller than ONE epoch of its own batch traffic — so the
TPU-native design uploads the corpus once and assembles every (B, L) batch
on-chip: ``jax.random.randint`` start offsets, a vmapped
``lax.dynamic_slice`` gather, and an ``astype(int32)`` widen, all inside the
jitted step.  Steady-state input cost is microseconds and zero host bytes.

``make_train_fn`` goes one step further and runs N optimizer steps per jit
call (``lax.scan``), so the host is touched once per N steps — the same
superstep trick ``DeviceCachedImages.make_epoch_fn``
uses, sized by steps instead of epochs because LM training samples windows
IID (the nanoGPT convention) rather than visiting examples exactly once.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


class DeviceCachedTokens:
    """HBM-resident token corpus with on-device batch assembly.

    Args:
      tokens: 1-D integer array (np.memmap from ``lm_corpus.load_token_bin``
        or any integer ndarray).  Stored on device as uint16 when the vocab
        fits (2 bytes/token), widened to int32 at gather time.
      mesh: optional Mesh; the corpus is replicated, batches are
        data-sharded via sharding constraints (same contract as the image
        cache).
    """

    def __init__(self, tokens, *, mesh=None, seed: int = 0,
                 default_seq_len: int | None = None):
        tokens = np.asarray(tokens)
        if tokens.ndim != 1:
            raise ValueError(f"token stream must be 1-D, got {tokens.shape}")
        if tokens.size < 2:
            raise ValueError("token stream too short")
        if np.issubdtype(tokens.dtype, np.integer) and tokens.dtype != np.uint16:
            # uint16 halves HBM + gather bytes; only when ids fit (a
            # negative sentinel would silently wrap to ~65535 otherwise).
            if tokens.size and 0 <= int(tokens.min()) and int(tokens.max()) < 2**16:
                tokens = tokens.astype(np.uint16)
        self.n = int(tokens.size)
        self.seed = seed
        self.mesh = mesh
        self.default_seq_len = default_seq_len
        self._samplers: dict = {}
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            self._tokens = jax.device_put(
                tokens, NamedSharding(mesh, PartitionSpec())
            )
        else:
            self._tokens = jax.device_put(tokens)

    def __len__(self) -> int:
        return self.n

    def _batch_sharding(self):
        from ..parallel.sharding import batch_sharding

        return batch_sharding(self.mesh, ndim=2)

    def sample_batch_fn(self, batch_size: int, seq_len: int):
        """Pure ``(tokens, key) -> (B, L) int32`` window sampler (traceable
        standalone or inside a scan)."""
        n, mesh = self.n, self.mesh
        if n < seq_len + 1:
            raise ValueError(f"corpus ({n} tokens) shorter than seq {seq_len}")
        sharding = self._batch_sharding() if mesh is not None else None

        def sample(tokens, key):
            # maxval is exclusive: n - seq_len must itself be drawable or
            # the stream's final token never appears in any window.
            starts = jax.random.randint(key, (batch_size,), 0, n - seq_len + 1)

            def window(s):
                return lax.dynamic_slice(tokens, (s,), (seq_len,))

            batch = jax.vmap(window)(starts).astype(jnp.int32)
            if sharding is not None:
                batch = lax.with_sharding_constraint(batch, sharding)
            return batch

        return sample

    def batches(self, epoch: int, batch_size: int, *,
                seq_len: int | None = None, steps: int | None = None):
        """Yield ``{"tokens": (B, L) int32}`` on-device batches for one
        "epoch" — the Trainer-compatible twin of
        ``DeviceCachedImages.batches`` (the CLI's ``--device-cache`` path).

        LM training samples windows IID (the nanoGPT convention), so an
        epoch here is ``steps`` draws (default: corpus tokens / tokens per
        batch — one nominal pass) with RNG derived from (seed, epoch, step);
        the host loop only threads jitted sampler calls, zero steady-state
        H2D bytes.
        """
        seq_len = seq_len or self.default_seq_len
        if seq_len is None:
            raise ValueError("seq_len required (or set default_seq_len)")
        if steps is None:
            steps = max(self.n // (batch_size * seq_len), 1)
        key_sig = (batch_size, seq_len)
        if key_sig not in self._samplers:
            self._samplers[key_sig] = jax.jit(
                self.sample_batch_fn(batch_size, seq_len)
            )
        sample = self._samplers[key_sig]
        base = jax.random.fold_in(jax.random.PRNGKey(self.seed), epoch)
        for step in range(steps):
            yield {"tokens": sample(self._tokens, jax.random.fold_in(base, step))}

    def make_train_fn(
        self, step_fn, batch_size: int, seq_len: int, *, steps_per_call: int
    ):
        """``run(state, superstep) -> (state, metrics)`` executing
        ``steps_per_call`` optimizer steps in one jitted scan.

        ``metrics`` maps each step_fn metric to its per-step values, shape
        ``(steps_per_call,)`` — callers get the full loss trajectory, not a
        mean that would hide divergence inside a superstep.  RNG is derived
        from (seed, superstep, step) so every window draw is deterministic
        and non-overlapping across supersteps.
        """
        sample = self.sample_batch_fn(batch_size, seq_len)
        seed = self.seed

        @partial(jax.jit, donate_argnums=0)
        def run(state, superstep):
            key = jax.random.fold_in(jax.random.PRNGKey(seed), superstep)

            def body(st, i):
                batch = {"tokens": sample(self._tokens, jax.random.fold_in(key, i))}
                st, m = step_fn(st, batch)
                return st, m

            return lax.scan(body, state, jnp.arange(steps_per_call))

        return run

    def make_eval_fn(
        self, eval_step, batch_size: int, seq_len: int, *,
        max_batches: int | None = None,
    ):
        """``evaluate(state) -> mean metrics`` over deterministic contiguous
        windows covering the (val) stream once — every token position
        scored exactly once, no sampling noise in the reported number."""
        n_seqs = self.n // seq_len
        n_batches = n_seqs // batch_size
        if max_batches is not None:
            n_batches = min(n_batches, max_batches)
        if n_batches == 0:
            raise ValueError(
                f"stream ({self.n} tokens) smaller than one eval batch "
                f"({batch_size}x{seq_len})"
            )
        mesh = self.mesh
        sharding = self._batch_sharding() if mesh is not None else None

        @jax.jit
        def evaluate(state):
            def body(carry, b):
                start = b * batch_size * seq_len
                flat = lax.dynamic_slice(
                    self._tokens, (start,), (batch_size * seq_len,)
                )
                batch = flat.reshape(batch_size, seq_len).astype(jnp.int32)
                if sharding is not None:
                    batch = lax.with_sharding_constraint(batch, sharding)
                m = eval_step(state, {"tokens": batch})
                return carry, m

            _, ms = lax.scan(body, None, jnp.arange(n_batches))
            return jax.tree_util.tree_map(lambda x: jnp.mean(x, axis=0), ms)

        return evaluate
