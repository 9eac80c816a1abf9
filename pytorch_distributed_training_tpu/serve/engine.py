"""AOT-compiled continuous-batching decode engine.

Three compiled device programs cover the whole serving loop, all over the
full slot array so shapes never change:

- **prefill**: one forward over an (S, C) chunk of prompt tokens — a TRUE
  batched prefill writing C cache positions per live row per call
  (replacing the one-token-per-tick teacher forcing of
  ``models/generate.py``), with per-row logits gathered at each row's last
  valid chunk column.  Long prompts take several chunks (chunked prefill —
  the scheduler interleaves these with decode ticks so live decodes aren't
  starved behind a long prompt).
- **decode**: one token per live slot, written at each slot's own position.
- **verify** (``spec_k > 0``): the speculative-decoding step — an
  (S, k+1) chunk per tick (the pending token plus up to k tokens proposed
  by the model-free prompt-lookup drafter, serve/draft.py), scored in ONE
  forward pass with greedy chain matching (or rejection-style acceptance
  under sampling), so accepted tokens cost one param/KV-cache read per
  tick instead of one each — the only way past the one-token-per-tick
  floor GEN_ROOFLINE (deleted: not measured on the current machine) pins decode
  at.  Greedy speculative output is
  TOKEN-EXACT vs the plain decode path; a rejected draft costs wasted
  compute, never a wrong token.  Rejected K/V writes are rolled back by
  length accounting (contiguous pool: stale bytes are unreachable by the
  ragged-mask contract) plus block freeing (paged pool:
  ``PagedKVCachePool.rewind``).

Idle rows ride along at the sentinel position (their K/V writes drop, their
outputs are discarded), so admission/retirement never retraces or
recompiles: the programs are lowered and compiled ONCE at construction
(``jax.jit(...).lower(...).compile()``), with the cache donated through
every call.

The engine host side owns per-slot request state: EOS/budget retirement,
generated-token buffers, and streaming (an optional ``stream_cb`` fires per
sampled token).  A served model is the same artifact training produces —
pass ``variables["params"]`` from init or the checkpoint restore path
(``cli/main.py --serve`` wires ``CheckpointManager.restore_params``, the
params-only restore that needs no optimizer template).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.signature import PROGRAM_REGISTRY, abstract_signature
from ..compat import named_scope
from ..models.generate import eos_cut_length, filter_logits, sample_logits
from ..obs.cost import mosaic_kernels
from ..obs.trace import phase_span
from .draft import NgramIndex, PromptLookupDrafter
from .kv_pool import KVCachePool, PagedKVCachePool, SlotExport
from .kv_store import HostKVStore


@dataclasses.dataclass(frozen=True)
class Event:
    """One observable step outcome: a streamed token or a finished request."""

    kind: str  # "token" | "finish"
    request_id: Any
    token: int | None = None
    reason: str | None = None  # finish only: "eos" | "length" | "cancelled"


@dataclasses.dataclass
class _Slot:
    request_id: Any
    prompt: np.ndarray
    max_new: int
    consumed: int = 0  # prompt tokens whose K/V are cached
    phase: str = "prefill"  # "prefill" | "decode"
    pending: int | None = None  # sampled token not yet fed back
    generated: list = dataclasses.field(default_factory=list)
    # Zero-accept drafting backoff: consecutive fully-rejected drafts
    # double the ticks this slot sits out before drafting again, so a
    # slot whose continuation just isn't draftable stops burning verify
    # width (a PARTIAL accept is still a win and resets the streak).
    spec_fail: int = 0
    spec_skip: int = 0

    def history(self) -> np.ndarray:
        """Every token of the sequence so far (prompt + generated, the
        last entry being the pending token about to be fed) — the
        drafter's lookup corpus."""
        return np.concatenate(
            [self.prompt, np.asarray(self.generated, np.int32)]
        ) if self.generated else self.prompt


@dataclasses.dataclass
class Handoff:
    """One request in flight from a prefill-role engine to a decode-role
    engine (serve/disagg.py): the host-side request state plus the KV
    handle (``SlotExport`` — a block-table row on the shared BlockPool,
    or a contiguous slot reference copied row-wise at adoption).  The
    decode engine adopts it without recomputing a single prompt
    position."""

    request_id: Any
    prompt: np.ndarray
    max_new: int
    generated: list
    pending: int
    export: SlotExport


class ServingEngine:
    """``paged=True`` swaps the contiguous per-slot cache for the block
    pool (``PagedKVCachePool``): the two AOT programs take the block table
    as a RUNTIME operand (admission/retirement/allocation never retrace),
    per-request length is bounded by the model's position table instead of
    ``prompt + budget <= max_len`` per slot, and shared prompt prefixes
    skip their prefill chunks via the pool's hash-addressed block cache.
    ``num_blocks`` defaults to the contiguous pool's byte equivalent
    (``num_slots * ceil(max_len / block_size)``)."""

    # Zero-accept drafting backoff: after F consecutive fully-rejected
    # drafts a slot sits out 2**F ticks (capped) before drafting again —
    # an undraftable continuation stops burning verify width, a partial
    # accept resets the streak.  Class attributes so experiments can tune
    # without threading more constructor args.
    SPEC_BACKOFF_CAP = 6

    def __init__(
        self,
        model,
        params,
        *,
        num_slots: int,
        max_len: int | None = None,
        prefill_chunk: int = 16,
        temperature: float = 0.0,
        top_k: int | None = None,
        exact_top_k: bool = False,
        eos_token_id: int | None = None,
        seed: int = 0,
        stream_cb: Callable[[Any, int], None] | None = None,
        paged: bool = False,
        block_size: int = 16,
        num_blocks: int | None = None,
        prefix_cache: bool = True,
        spec_k: int = 0,
        spec_ngram: int = 4,
        tp_mesh=None,
        role: str = "both",
        block_pool=None,
        kv_host_mb: float | None = None,
        kv_dtype: str = "bf16",
    ):
        from ..comm.compress import KV_DTYPES

        if prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if kv_dtype not in KV_DTYPES:
            raise ValueError(
                f"kv_dtype must be one of {KV_DTYPES}, got {kv_dtype!r}"
            )
        if kv_dtype != "bf16" and not paged:
            raise ValueError(
                "quantized KV storage lives in the paged block pool — "
                "pass paged=True with kv_dtype int8/int4"
            )
        if role not in ("both", "prefill", "decode"):
            raise ValueError(
                f"role must be 'both', 'prefill' or 'decode', got {role!r}"
            )
        if block_pool is not None and not paged:
            raise ValueError(
                "block_pool sharing is the paged layout's handoff "
                "substrate — pass paged=True"
            )
        if kv_host_mb is not None and not paged:
            raise ValueError(
                "the host KV tier spills paged blocks — pass paged=True"
            )
        if kv_host_mb is not None and block_pool is not None:
            raise ValueError(
                "on a SHARED BlockPool the host tier belongs to the pool "
                "— construct it there (BlockPool(host_store=...)), not on "
                "one of its views"
            )
        # Disaggregated serving (serve/disagg.py): a "prefill"-role
        # engine compiles ONLY the chunked-prefill program and hands
        # finished prompts off (``export_handoff``) instead of decoding;
        # a "decode"-role engine compiles the decode (+verify) programs
        # and admits exclusively by ``adopt``.  "both" is the original
        # interleaved engine.  The MPMD program-per-role decomposition:
        # each role's executables are their own compiled artifacts.
        self.role = role
        # Tensor-parallel serving (``tp_mesh``, parallel/sharding.
        # serve_tp_mesh): all three AOT programs compile against
        # NamedShardings over the mesh — params laid out by
        # ``serve_tp_rules()`` (column/row megatron splits with every
        # deliberate replication explicit; GSPMD inserts the
        # collectives), both KV pool layouts sharded on the
        # heads axis (attention is head-local, so K/V arrive from the
        # column-split QKV already owned by the right shard), and every
        # host-fed operand (tokens, positions, block tables, rng)
        # replicated.  The donation/AOT contract is unchanged: lowered +
        # compiled once, cache donated, admission never retraces.  A
        # single-device mesh (tp=1) shards nothing but still PLACES the
        # replica's params/cache/programs on its own device — the N-
        # replica router's MPMD layout.  Greedy output is token-exact vs
        # the unsharded engine (column/row splits reproduce the exact
        # per-logit dot up to the deterministic psum order; pinned by
        # tests/test_serve_tp.py).
        self.tp_mesh = tp_mesh
        self.params = params
        self.eos_token_id = eos_token_id
        self.prefill_chunk = prefill_chunk
        self.stream_cb = stream_cb
        # Quantized KV storage (--serve-kv-dtype): "bf16" = native-dtype
        # status quo (the f32 CPU proxy stores f32); int8/int4 thread
        # ``kv_quant`` through the decoder so the cache skeleton carries
        # the stored width + scale leaves, the write scatter encodes, and
        # the paged Pallas kernels dequantize in VMEM.
        self.kv_dtype = kv_dtype
        self._kv_quant = None if kv_dtype == "bf16" else kv_dtype
        clone_kw: dict = dict(decode=True, tp_mesh=tp_mesh)
        if self._kv_quant is not None:
            clone_kw["kv_quant"] = self._kv_quant
        self._decoder = model.clone(**clone_kw)
        self.paged = paged
        # Speculative decoding (spec_k > 0): up to spec_k prompt-lookup
        # draft tokens verified per decode tick.  The drafter is a plain
        # attribute so tests can inject a scripted one.  min_ngram rides
        # one below the max (floored at 2): longest-match-first with a
        # single fallback level — looser floors draft noise that verifies
        # to nothing, tighter ones miss the short-period repetition that
        # is the drafter's bread and butter (swept in rounds 1-5, another
        # machine).
        self.spec_k = spec_k
        self.spec_ngram = spec_ngram
        # A prefill-role engine never decodes, so it neither drafts nor
        # compiles the verify program (spec_k is inert there).
        self.drafter = PromptLookupDrafter(
            max_ngram=spec_ngram,
            # clamped so spec_ngram=1 stays constructible (floor can
            # never exceed the ceiling)
            min_ngram=min(max(2, spec_ngram - 1), spec_ngram),
            index=NgramIndex(spec_ngram),
        ) if spec_k > 0 and role != "prefill" else None
        cap = max_len or model.cfg.max_seq_len
        if paged:
            host = None
            if kv_host_mb is not None:
                # The host-RAM KV tier (serve/kv_store.py): evicted
                # refcount-0 prefix blocks spill there and restore on a
                # hash-chain hit instead of recomputing.  (On a SHARED
                # BlockPool the tier is the pool's — guarded above.)
                host = HostKVStore(int(kv_host_mb * 2**20))
            self.pool = PagedKVCachePool(
                self._decoder, num_slots=num_slots,
                num_blocks=(
                    None if block_pool is not None
                    else num_blocks or num_slots * (-(-cap // block_size))
                ),
                block_size=None if block_pool is not None else block_size,
                max_len=cap, prefix_cache=prefix_cache,
                blocks=block_pool, host_store=host,
            )
        else:
            self.pool = KVCachePool(
                self._decoder, num_slots=num_slots, max_len=cap,
            )
        if paged and block_pool is not None:
            # A view over a SHARED BlockPool must agree with the pool
            # about the storage dtype — the arrays are the substrate's,
            # and a mismatched view would trace against wrong shapes.
            # The payload dtype identifies the rung exactly (int8 !=
            # nibble-packed uint8 != native float), so an int8 view over
            # an int4 pool fails HERE with a clear error, not deep in
            # tracing.
            payload = next(
                leaf
                for p, leaf in jax.tree_util.tree_leaves_with_path(
                    block_pool.cache
                )
                if getattr(p[-1], "key", None) == "cached_key"
            )
            pool_quant = {
                jnp.dtype(jnp.int8): "int8", jnp.dtype(jnp.uint8): "int4",
            }.get(jnp.dtype(payload.dtype))
            if pool_quant != self._kv_quant:
                raise ValueError(
                    f"kv_dtype {kv_dtype!r} disagrees with the shared "
                    f"BlockPool's storage layout ({pool_quant or 'bf16'})"
                    " — construct the pool and every view with one "
                    "kv_dtype"
                )
        self.max_len = self.pool.max_len
        self.num_slots = num_slots
        # Host-side admission cap (serve/autoscale.py re-split seam):
        # when set below ``num_slots``, admission/adoption stop at the
        # cap while the compiled programs keep running at their built
        # width (excess rows are just idle-masked — zero new compiles).
        # None = uncapped.
        self.slot_cap: int | None = None
        self._slots: list[_Slot | None] = [None] * num_slots
        self._seed = seed
        self._rng = jax.random.PRNGKey(seed)
        self._replicated = None
        self._cache_shardings = None
        if tp_mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            from ..parallel.sharding import (
                infer_params_sharding, kv_cache_sharding, serve_tp_rules,
            )

            self._replicated = NamedSharding(tp_mesh, PartitionSpec())
            self.params = jax.device_put(
                params,
                infer_params_sharding(params, tp_mesh, serve_tp_rules()),
            )
            self._cache_shardings = kv_cache_sharding(
                self.pool.cache, tp_mesh
            )
            self.pool.place(self._cache_shardings)
            self._rng = jax.device_put(self._rng, self._replicated)
        self._sample_kw = dict(
            temperature=temperature, top_k=top_k, exact_top_k=exact_top_k
        )
        self.prefill_tokens_computed = 0
        self.prefill_tokens_offered = 0
        # Decode-side accounting (obs spine + bench): ticks/tokens through
        # the decode-or-verify path, plus the speculation counters.
        self.decode_ticks = 0
        self.decode_slot_ticks = 0  # one per LIVE decoding slot per tick
        self.decode_tokens = 0
        self.spec_drafted_tokens = 0
        self.spec_accepted_tokens = 0
        # Span recorder (obs/spans.py), wired by the scheduler when the
        # run traces: every compiled-program tick records a slot-
        # attributed host span (serve/prefill, serve/decode, serve/verify)
        # bracketing dispatch + the token fetch's device sync.  None costs
        # nothing on the tick path.  ``spans_replica`` (also stamped by
        # the scheduler) rides the tick spans so the exporter can group
        # slot tracks under the owning replica's process row.
        self.spans = None
        self.spans_replica = None
        # Abstract-signature hash per AOT program (graftcheck's recompile
        # guard pins each to exactly one compile over a scheduler trace).
        self.program_signatures: dict[str, str] = {}
        self._prefill_fn, self._decode_fn, self._verify_fn = self._compile()

    # ------------------------------------------------------------------ #
    # compiled steps
    # ------------------------------------------------------------------ #

    def _compile(self):
        decoder, pool = self._decoder, self.pool
        s, c = self.num_slots, self.prefill_chunk
        kw = self._sample_kw
        mask_len = pool.mask_len
        paged = self.paged

        def slot_mask(positions, width):
            # The slot-mode ragged/causal validity, computed ONCE per tick
            # here and threaded through every layer (each block otherwise
            # re-derives the identical iota compare against the cache
            # window) — the device-side face of the pool's incrementally-
            # maintained host valid_mask.
            cols = positions[:, None] + jnp.arange(width)[None, :]
            return (
                jnp.arange(mask_len)[None, None, :] <= cols[:, :, None]
            )  # (S, width, mask_len)

        def apply_step(params, cache, tokens, positions, table):
            mask = slot_mask(positions, tokens.shape[1])
            return decoder.apply(
                {"params": params, "cache": cache}, tokens,
                train=False, mutable=["cache"], positions=positions,
                block_table=table, attn_mask=mask,
            )

        def prefill(params, cache, tokens, positions, last_idx, table, rng):
            # tokens (S, C); positions (S,) chunk start (sentinel = idle);
            # last_idx (S,) column of each row's last valid token; table
            # (S, nb) block table (paged) or None — a runtime operand, so
            # block allocation/sharing never retraces.
            with named_scope("serve/prefill"):
                logits, upd = apply_step(
                    params, cache, tokens, positions, table
                )
            last = jnp.take_along_axis(
                logits, last_idx[:, None, None], axis=1
            )[:, 0]
            rng, key = jax.random.split(rng)
            tok = sample_logits(last, key, **kw)
            return upd["cache"], tok, rng

        def decode(params, cache, tokens, positions, table, rng):
            with named_scope("serve/decode"):
                logits, upd = apply_step(
                    params, cache, tokens[:, None], positions, table
                )
            rng, key = jax.random.split(rng)
            tok = sample_logits(logits[:, 0], key, **kw)
            return upd["cache"], tok, rng

        # Greedy iff sample_logits would argmax — the SAME rule, so the
        # verify program's acceptance test cannot drift from sampling.
        greedy = kw["temperature"] == 0.0 or kw["top_k"] == 1
        k1 = self.spec_k + 1

        def verify(params, cache, tokens, positions, draft_len, table, rng):
            # tokens (S, k+1): column 0 = the pending token, columns
            # 1..draft_len[s] = the drafted continuation, rest padding.
            # One forward scores every position; acceptance keeps the
            # longest draft prefix the model agrees with, plus one bonus
            # token from the first disagreeing (or final) position — so a
            # tick emits 1..k+1 tokens per slot for ONE param/cache read.
            with named_scope("serve/verify"):
                logits, upd = apply_step(
                    params, cache, tokens, positions, table
                )
            draft = tokens[:, 1:]  # (S, k)
            in_draft = (
                jnp.arange(k1 - 1)[None, :] < draft_len[:, None]
            )
            if greedy:
                # chain[s, j] = greedy next token after consuming
                # tokens[s, :j+1]; an accepted draft token EQUALS its
                # chain entry, so the emission is simply chain[:, :m+1]
                # — token-exact vs the non-speculative engine.
                chain = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                ok = (chain[:, :-1] == draft) & in_draft
                accepted = jnp.sum(
                    jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1
                )
                out = chain
            else:
                # Rejection-style acceptance for a DETERMINISTIC drafter
                # (q = delta at the draft token): accept d_j with
                # probability p_j(d_j) under the same filtered/tempered
                # distribution sample_logits draws from; on the first
                # rejection, sample the bonus from the residual
                # (p with d_j's mass removed, renormalized) — the emitted
                # tokens are distributed exactly as non-speculative
                # sampling, draft quality only moves throughput.
                filt = filter_logits(
                    logits, temperature=kw["temperature"],
                    top_k=kw["top_k"], exact_top_k=kw["exact_top_k"],
                )
                probs = jax.nn.softmax(filt, axis=-1)
                rng, ku, kb = jax.random.split(rng, 3)
                u = jax.random.uniform(ku, draft.shape)
                p_draft = jnp.take_along_axis(
                    probs[:, :-1], draft[..., None], axis=-1
                )[..., 0]
                ok = (u < p_draft) & in_draft
                accepted = jnp.sum(
                    jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1
                )
                bonus_probs = jnp.take_along_axis(
                    probs, accepted[:, None, None], axis=1
                )[:, 0]  # (S, V) at the first rejected / final position
                rejected_tok = jnp.take_along_axis(
                    draft, jnp.clip(accepted, 0, k1 - 2)[:, None], axis=1
                )[:, 0]
                was_rejection = accepted < draft_len
                vocab = jnp.arange(bonus_probs.shape[-1])
                residual = jnp.where(
                    was_rejection[:, None]
                    & (vocab[None, :] == rejected_tok[:, None]),
                    0.0, bonus_probs,
                )
                bonus = jax.random.categorical(
                    kb, jnp.log(residual), axis=-1
                ).astype(jnp.int32)
                draft_pad = jnp.concatenate(
                    [draft, jnp.zeros((s, 1), jnp.int32)], axis=1
                )
                out = jnp.where(
                    jnp.arange(k1)[None, :] < accepted[:, None],
                    draft_pad, bonus[:, None],
                )
            return upd["cache"], out, accepted.astype(jnp.int32), rng

        tp = self.tp_mesh is not None
        rep = self._replicated
        abs_of = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype,
                sharding=(
                    x.sharding if tp and isinstance(x, jax.Array) else None
                ),
            ), t
        )
        i32 = lambda shape: jax.ShapeDtypeStruct(  # noqa: E731
            shape, jnp.int32, sharding=rep if tp else None
        )
        table_abs = (
            i32((s, pool.blocks_per_slot)) if paged else None
        )
        # TP: inputs carry their shardings through the abstract values
        # (params = tp_rules, cache = heads-axis, operands replicated) and
        # out_shardings pin the outputs — the donated cache keeps its
        # layout (donation requires it) and sampled tokens come back
        # replicated so the host reads them without a gather.
        jit_kw: dict = dict(donate_argnums=(1,))
        jit_kw3 = dict(jit_kw)
        jit_kw4 = dict(jit_kw)
        if tp:
            cshard = self._cache_shardings
            jit_kw3["out_shardings"] = (cshard, rep, rep)
            jit_kw4["out_shardings"] = (cshard, rep, rep, rep)
        # AOT: lowered + compiled once, cache donated every call — admission
        # and retirement are pure host bookkeeping, never a retrace.
        # Every compile records its abstract signature into the graftcheck
        # recompile guard (analysis/signature.py): a full scheduler trace
        # must leave each program's compile count at exactly one, and
        # ``program_signatures`` is the per-engine hash the HLO audit
        # reports.
        def aot(name, lowered):
            sig = abstract_signature(lowered)
            self.program_signatures[name] = sig
            PROGRAM_REGISTRY.record(f"serve/{name}", sig)
            return lowered.compile()

        # Role gating (serve/disagg.py): each role compiles ONLY its own
        # programs — the MPMD program-per-role split.  A prefill-role
        # engine has no decode/verify executable at all (its slots hand
        # off at prompt completion); a decode-role engine never prefills
        # (it admits by adoption).
        prefill_c = decode_c = verify_c = None
        if self.role in ("both", "prefill"):
            prefill_c = aot("prefill", jax.jit(prefill, **jit_kw3).lower(
                abs_of(self.params), abs_of(pool.cache),
                i32((s, c)), i32((s,)), i32((s,)), table_abs,
                abs_of(self._rng),
            ))
        if self.role in ("both", "decode"):
            decode_c = aot("decode", jax.jit(decode, **jit_kw3).lower(
                abs_of(self.params), abs_of(pool.cache),
                i32((s,)), i32((s,)), table_abs, abs_of(self._rng),
            ))
            if self.spec_k > 0:
                verify_c = aot("verify", jax.jit(verify, **jit_kw4).lower(
                    abs_of(self.params), abs_of(pool.cache),
                    i32((s, k1)), i32((s,)), i32((s,)), table_abs,
                    abs_of(self._rng),
                ))
        return prefill_c, decode_c, verify_c

    # ------------------------------------------------------------------ #
    # slot admission / retirement
    # ------------------------------------------------------------------ #

    @property
    def mosaic_kernels(self) -> dict[str, dict[str, int]]:
        """Pallas TPU kernels per compiled program, by kernel name
        (obs/cost.py; the names are ``ops.pallas_attention.KERNEL_NAMES``):
        what the kernel dispatch actually lowered.  Read from the program
        text on demand — one caller (the CLI's start-up line) wants it."""
        programs = {
            "prefill": self._prefill_fn, "decode": self._decode_fn,
            "verify": self._verify_fn,
        }
        return {
            name: mosaic_kernels(fn.as_text())
            for name, fn in programs.items() if fn is not None
        }

    @property
    def mosaic_custom_calls(self) -> dict[str, int]:
        """:attr:`mosaic_kernels` summed per program."""
        return {
            name: sum(kernels.values())
            for name, kernels in self.mosaic_kernels.items()
        }

    @property
    def effective_slots(self) -> int:
        """Admission width: ``num_slots`` unless a re-split capped it."""
        if self.slot_cap is None:
            return self.num_slots
        return min(self.slot_cap, self.num_slots)

    @property
    def has_free_slot(self) -> bool:
        return self.pool.num_active < self.effective_slots

    @property
    def busy(self) -> bool:
        return self.pool.num_active > 0

    def validate_request(self, prompt_len: int, max_new: int) -> None:
        """Raise for a request that could NEVER be admitted — over the
        logical position bound, or (paged) a zero-hit worst-case span
        larger than the whole block pool.  Queueing such a request would
        head-of-line-block the scheduler forever, so it must be refused
        at submit/start time."""
        if prompt_len + max_new > self.max_len:
            raise ValueError(
                f"prompt ({prompt_len}) + max_new ({max_new}) exceeds the "
                f"cache length ({self.max_len})"
            )
        if self.paged and not self.pool.fits(prompt_len, max_new):
            raise ValueError(
                f"prompt ({prompt_len}) + max_new ({max_new}) spans more "
                f"blocks than the whole pool ({self.pool.num_blocks} x "
                f"{self.pool.block_size}) — the request can never be "
                "admitted"
            )

    def can_admit(self, prompt, max_new: int) -> bool:
        """Whether ``start`` would succeed NOW: a free slot (contiguous),
        plus — paged — enough unreserved blocks for the request's
        worst-case span net of its prefix-cache hits.  The scheduler's
        admission predicate (it replaces the free-slot-only check)."""
        if not self.has_free_slot:
            return False
        if self.paged:
            return self.pool.admissible_for(
                np.asarray(prompt, np.int32).reshape(-1), int(max_new)
            )
        return True

    def start(self, request_id, prompt, max_new: int) -> int:
        """Admit a request into a free slot; returns the slot index."""
        if self.role == "decode":
            raise RuntimeError(
                "a decode-role engine admits by adopt() — it has no "
                "prefill program to consume a raw prompt with"
            )
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        self.validate_request(prompt.size, int(max_new))
        if self.paged:
            slot, cached = self.pool.allocate(prompt, int(max_new))
        else:
            slot = self.pool.allocate()
            cached = 0
        if slot is None:
            raise RuntimeError("no free slot (check has_free_slot first)")
        self.prefill_tokens_offered += int(prompt.size)
        if self.drafter is not None:
            # Cross-request drafting: the admitted prompt feeds the shared
            # n-gram index (serve/draft.py) — the token-level analogue of
            # the paged pool's hash-chained prefix sharing.
            self.drafter.observe_prompt(prompt)
        self._slots[slot] = _Slot(
            request_id=request_id, prompt=prompt, max_new=int(max_new),
            consumed=cached,
        )
        return slot

    def _live(self, phase: str) -> list[tuple[int, _Slot]]:
        return [
            (i, sl) for i, sl in enumerate(self._slots)
            if sl is not None and sl.phase == phase
        ]

    # ------------------------------------------------------------------ #
    # prefill->decode handoff (serve/disagg.py)
    # ------------------------------------------------------------------ #

    def handoff_ready(self) -> list[int]:
        """Slots whose prompt finished prefilling on this prefill-role
        engine and now await adoption by a decode-role engine."""
        return [i for i, _ in self._live("handoff")]

    def export_handoff(self, slot: int) -> Handoff:
        """Detach a finished-prefill request for decode-side adoption:
        the request state plus the pool's KV handle (paged: the block
        table row — zero copy, the slot frees immediately; contiguous:
        a row reference copied at adoption).  No program runs and no
        shape changes — the recompile guard pins zero compiles across a
        handoff."""
        sl = self._slots[slot]
        if sl is None or sl.phase != "handoff":
            raise ValueError(f"slot {slot} is not awaiting handoff")
        handoff = Handoff(
            request_id=sl.request_id, prompt=sl.prompt, max_new=sl.max_new,
            generated=list(sl.generated), pending=int(sl.pending),
            export=self.pool.export_slot(slot),
        )
        self._slots[slot] = None
        return handoff

    def can_adopt(self) -> bool:
        return self.has_free_slot

    def adopt(self, handoff: Handoff) -> int:
        """Adopt a handed-off request into this decode-role engine: the
        pool installs the KV handle (no recompute — the prompt's K/V
        arrive as written by the prefill side) and the slot resumes at
        the pending token exactly where the interleaved engine would
        have."""
        slot = self.pool.adopt_slot(handoff.export)
        self._slots[slot] = _Slot(
            request_id=handoff.request_id, prompt=handoff.prompt,
            max_new=handoff.max_new, consumed=handoff.prompt.size,
            phase="decode", pending=handoff.pending,
            generated=list(handoff.generated),
        )
        if self.drafter is not None:
            # The decode side owns the drafter: the adopted prompt feeds
            # the shared n-gram index here (admission happened on the
            # prefill engine, which has none).
            self.drafter.observe_prompt(handoff.prompt)
        return slot

    def live_requests(self) -> list:
        """Request ids of every in-flight (admitted, unfinished) request —
        the scheduler's cancellation sweep iterates these."""
        return [
            sl.request_id for sl in self._slots if sl is not None
        ]

    def cancel(self, request_id) -> Event:
        """Retire an in-flight request NOW with finish reason
        ``"cancelled"``, freeing its slot (and, paged, its block-table
        blocks back to the pool) instead of letting it run to completion
        — the mid-decode half of ``--serve-ttl``'s deadline contract (the
        queued half is the scheduler's shed)."""
        for i, sl in enumerate(self._slots):
            if sl is not None and sl.request_id == request_id:
                return self._retire(i, sl, "cancelled")
        raise KeyError(f"request {request_id!r} is not in flight")

    def _retire(self, slot: int, sl: _Slot, reason: str) -> Event:
        self._slots[slot] = None
        self.pool.release(slot)
        return Event("finish", sl.request_id, reason=reason)

    def _emit(self, slot: int, sl: _Slot, token: int) -> list[Event]:
        """Record one sampled token for ``slot``: stream it, then either
        retire (EOS / budget) or queue it as the next decode input."""
        sl.generated.append(token)
        if self.stream_cb is not None:
            self.stream_cb(sl.request_id, token)
        events = [Event("token", sl.request_id, token=token)]
        if self.eos_token_id is not None and token == self.eos_token_id:
            events.append(self._retire(slot, sl, "eos"))
        elif len(sl.generated) >= sl.max_new:
            events.append(self._retire(slot, sl, "length"))
        else:
            sl.pending = token
        return events

    # ------------------------------------------------------------------ #
    # iteration-level steps
    # ------------------------------------------------------------------ #

    def _dev(self, x):
        """One per-tick host operand: committed jnp array off-TP (the
        status quo), raw numpy under TP — the compiled executable places
        numpy against its replicated input sharding, while a
        ``jnp.asarray`` here would commit to one device and fail the AOT
        call's strict sharding check."""
        return np.ascontiguousarray(x) if self.tp_mesh is not None \
            else jnp.asarray(x)

    def _table_operand(self):
        """The block table as a device operand (paged), else None — either
        way a RUNTIME argument of the compiled steps, so per-tick
        allocation changes never retrace."""
        if not self.paged:
            return None
        return self._dev(self.pool.block_tables)

    def prefill_step(self) -> list[Event]:
        """Advance every prefilling slot by one chunk (one compiled call).
        A slot whose prompt completes samples its FIRST output token here —
        that sample is the TTFT moment."""
        batch = self._live("prefill")
        if not batch:
            return []
        s, c = self.num_slots, self.prefill_chunk
        tokens = np.zeros((s, c), np.int32)
        positions = np.full((s,), self.pool.sentinel, np.int32)
        last_idx = np.zeros((s,), np.int32)
        took = {}
        for i, sl in batch:
            n = min(c, sl.prompt.size - sl.consumed)
            tokens[i, :n] = sl.prompt[sl.consumed:sl.consumed + n]
            positions[i] = self.pool.lengths[i]
            last_idx[i] = n - 1
            took[i] = n
            if self.paged:
                self.pool.ensure_length(i, int(self.pool.lengths[i]) + n)
        # Slot attribution rides the span: [slot, request id, tokens this
        # chunk] — the exporter fans these out to per-slot tracks and the
        # TTFT decomposition charges each request its chunks' wall time.
        # Attrs are built only when a span will record: the untraced tick
        # path pays nothing beyond the annotation.
        span_kw = {}
        if self.spans is not None:
            span_kw["slots"] = [[i, sl.request_id, took[i]] for i, sl in batch]
            if self.spans_replica is not None:
                span_kw["replica"] = self.spans_replica
        with phase_span(self.spans, "serve/prefill", **span_kw):
            cache, tok, rng = self._prefill_fn(
                self.params, self.pool.cache, self._dev(tokens),
                self._dev(positions), self._dev(last_idx),
                self._table_operand(), self._rng,
            )
            self.pool.cache, self._rng = cache, rng
            tok = np.asarray(tok)  # device sync: the span closes on real work
        events: list[Event] = []
        for i, sl in batch:
            sl.consumed += took[i]
            self.prefill_tokens_computed += took[i]
            self.pool.advance(i, took[i])
            if sl.consumed == sl.prompt.size:
                # A prefill-role engine parks the finished prompt for
                # handoff instead of decoding it; the first token (the
                # TTFT moment) is still sampled and emitted HERE — the
                # decode side starts from the pending token.  EOS or a
                # one-token budget retires on this side outright.
                sl.phase = "handoff" if self.role == "prefill" else "decode"
                events.extend(self._emit(i, sl, int(tok[i])))
        return events

    def decode_step(self) -> list[Event]:
        """One token for every decoding slot (one compiled call)."""
        batch = self._live("decode")
        if not batch:
            return []
        tokens = np.zeros((self.num_slots,), np.int32)
        positions = np.full((self.num_slots,), self.pool.sentinel, np.int32)
        for i, sl in batch:
            tokens[i] = sl.pending
            positions[i] = self.pool.lengths[i]
            if self.paged:
                self.pool.ensure_length(i, int(self.pool.lengths[i]) + 1)
        span_kw = {}
        if self.spans is not None:
            span_kw["slots"] = [[i, sl.request_id] for i, sl in batch]
            if self.spans_replica is not None:
                span_kw["replica"] = self.spans_replica
        with phase_span(self.spans, "serve/decode", **span_kw):
            cache, tok, rng = self._decode_fn(
                self.params, self.pool.cache, self._dev(tokens),
                self._dev(positions), self._table_operand(), self._rng,
            )
            self.pool.cache, self._rng = cache, rng
            tok = np.asarray(tok)  # device sync: the span closes on real work
        events: list[Event] = []
        self.decode_ticks += 1
        self.decode_slot_ticks += len(batch)
        for i, sl in batch:
            self.pool.advance(i, 1)
            self.decode_tokens += 1
            events.extend(self._emit(i, sl, int(tok[i])))
        return events

    def verify_step(self) -> list[Event]:
        """Speculative decode tick: draft up to ``spec_k`` tokens per
        decoding slot (prompt lookup, serve/draft.py), score all k+1
        positions in one compiled verify call, and emit every accepted
        token plus the bonus — 1..k+1 tokens per slot for one param/cache
        read.  Ticks where NO slot drafted fall back to the plain decode
        program (same emission, (k+1)x less score compute).

        Rollback of rejected writes: lengths advance only by the emitted
        token count, so rejected K/V land past every slot's valid length
        (unreachable stale bytes, the ragged-mask contract); the paged
        pool additionally frees blocks that only rejected tokens touched
        (``rewind`` — shared refcounted prefix blocks are structurally
        below the live length and never touched)."""
        batch = self._live("decode")
        if not batch:
            return []
        s, k1 = self.num_slots, self.spec_k + 1
        tokens = np.zeros((s, k1), np.int32)
        positions = np.full((s,), self.pool.sentinel, np.int32)
        dlen = np.zeros((s,), np.int32)
        for i, sl in batch:
            tokens[i, 0] = sl.pending
            positions[i] = self.pool.lengths[i]
            # Draft cap: the budget bounds emission (emitting past
            # max_new is pure waste) and the position table bounds writes.
            room = min(
                sl.max_new - len(sl.generated) - 1,
                self.max_len - int(self.pool.lengths[i]) - 1,
                self.spec_k,
            )
            if sl.spec_skip > 0:
                sl.spec_skip -= 1
                continue
            draft = self.drafter.draft(sl.history(), room)
            n = int(draft.size)
            if n:
                tokens[i, 1:1 + n] = draft
                dlen[i] = n
                self.spec_drafted_tokens += n
        if not dlen.any():
            # Cold tick (no slot found a draftable suffix): the plain
            # decode program does the identical job without the (k+1)-wide
            # score — this fallback is what keeps the adversarial
            # zero-hit workload within a few percent of the baseline.
            return self.decode_step()
        for i, sl in batch:
            if self.paged:
                self.pool.ensure_length(
                    i, int(self.pool.lengths[i]) + int(dlen[i]) + 1
                )
        span_kw = {}
        if self.spans is not None:
            span_kw["slots"] = [[i, sl.request_id] for i, sl in batch]
            span_kw["drafted"] = int(dlen.sum())
            if self.spans_replica is not None:
                span_kw["replica"] = self.spans_replica
        with phase_span(self.spans, "serve/verify", **span_kw) as vspan:
            cache, out, accepted, rng = self._verify_fn(
                self.params, self.pool.cache, self._dev(tokens),
                self._dev(positions), self._dev(dlen),
                self._table_operand(), self._rng,
            )
            self.pool.cache, self._rng = cache, rng
            out = np.asarray(out)
            accepted = np.asarray(accepted)  # device sync closes the span
            if vspan is not None:
                vspan.attrs["accepted"] = int(accepted[
                    [i for i, _ in batch]
                ].sum())
        events: list[Event] = []
        self.decode_ticks += 1
        self.decode_slot_ticks += len(batch)
        for i, sl in batch:
            m = int(accepted[i])
            self.spec_accepted_tokens += m
            if dlen[i]:
                if m == 0:
                    sl.spec_fail = min(
                        sl.spec_fail + 1, self.SPEC_BACKOFF_CAP
                    )
                    sl.spec_skip = 2 ** sl.spec_fail
                else:
                    sl.spec_fail = 0
            emit = out[i, :m + 1]
            # One EOS-in-draft rule, shared with generate()'s early-exit
            # accounting: an EOS inside the accepted span retires the slot
            # AT the EOS position, never after the full k.
            emit = emit[:eos_cut_length(emit, self.eos_token_id)]
            # Claim exactly the consumed positions: the pending token plus
            # the emitted-minus-one accepted drafts (the final emitted
            # token is the next INPUT — bonus, EOS, or budget end — whose
            # K/V is not yet needed).  Everything past this is a rejected
            # write, unreachable by the ragged mask.
            self.pool.advance(i, int(emit.size))
            self.decode_tokens += int(emit.size)
            if self.paged:
                self.pool.rewind(i)
            for t in emit:
                events.extend(self._emit(i, sl, int(t)))
                if self._slots[i] is None:  # retired (EOS / budget)
                    break
        return events

    def step(self) -> list[Event]:
        """One engine tick.  ``role="both"``: a prefill chunk for
        prompt-loading slots, then a decode (or speculative verify)
        token batch for generating slots — the iteration-level
        interleave (decoders advance every tick even while a long prompt
        chunks in).  Role engines run only their own half; the
        disaggregated tier (serve/disagg.py) sequences them."""
        if self.role == "prefill":
            return self.prefill_step()
        decode = (
            self.verify_step if self._verify_fn is not None
            else self.decode_step
        )
        if self.role == "decode":
            return decode()
        return self.prefill_step() + decode()

    def stats(self) -> dict:
        """Host-side accounting for the obs spine and the bench: prefill
        work actually computed vs offered (the prefix-cache saving), plus
        the paged pool's block/hit/eviction counters when paged."""
        out = {
            "slots_active": self.pool.num_active,
            "slot_cap": self.effective_slots,
            "prefill_tokens_computed": self.prefill_tokens_computed,
            "prefill_tokens_offered": self.prefill_tokens_offered,
            "decode_ticks": self.decode_ticks,
            "decode_slot_ticks": self.decode_slot_ticks,
            "decode_tokens": self.decode_tokens,
        }
        if self.spec_k > 0:
            out["spec_drafted_tokens"] = self.spec_drafted_tokens
            out["spec_accepted_tokens"] = self.spec_accepted_tokens
        if self.paged:
            out.update(self.pool.stats())
        return out

    def memory_model(self, program: str) -> dict[str, int]:
        """Analytic per-device HBM byte model for one compiled program
        (graftcheck pass 3's memory audit pins ``memory_analysis()``
        against this).

        Components are computed from the engine's CONFIG and declared
        layout intent — params under ``serve_tp_rules`` over the TP
        submesh, the KV pool under ``kv_cache_sharding``, host operands
        replicated — never from the compiled artifact, so a program
        whose actual footprint drifts (a pool compiled at the wrong
        layout, donation silently unaliased, replicated shards of a
        sharded param) disagrees with the model instead of redefining
        it.  ``kv_cache_model`` is the pure closed-form pool size
        (``obs.cost.kv_pool_model_bytes``); the audit asserts it equals
        the tree-derived ``kv_cache`` so the two byte models cannot
        drift apart silently.
        """
        import numpy as _np

        from ..obs.cost import (
            kv_heads_shard, kv_pool_model_bytes,
            serve_activation_estimate, tree_bytes_per_device,
        )

        if program not in ("prefill", "decode", "verify"):
            raise ValueError(f"unknown program {program!r}")
        cfg = self._decoder.cfg
        tp_size = self.tp_mesh.devices.size if self.tp_mesh is not None \
            else 1
        if self.tp_mesh is not None:
            from ..parallel.sharding import (
                kv_cache_sharding, serve_tp_rules,
            )

            params_dev = tree_bytes_per_device(
                self.params, mesh=self.tp_mesh, rules=serve_tp_rules(),
            )
            cache_dev = tree_bytes_per_device(
                self.pool.cache,
                shardings=kv_cache_sharding(self.pool.cache, self.tp_mesh),
            )
        else:
            params_dev = tree_bytes_per_device(self.params)
            cache_dev = tree_bytes_per_device(self.pool.cache)
        # Closed-form pool size for the drift check: K/V leaves only —
        # the index/control leaves are whatever remains of the tree.
        from .kv_pool import _is_kv_leaf

        kv_leaf_bytes = sum(
            _np.prod(l.shape, dtype=_np.int64) * l.dtype.itemsize
            for path, l in jax.tree_util.tree_leaves_with_path(
                self.pool.cache
            )
            if _is_kv_leaf(path)
        )
        head_dim = cfg.hidden_dim // cfg.num_heads
        kv_model = kv_pool_model_bytes(
            num_layers=cfg.num_layers, num_heads=cfg.num_heads,
            head_dim=head_dim, max_len=self.pool.max_len,
            num_slots=self.num_slots, paged=self.paged,
            num_blocks=getattr(self.pool, "num_blocks", 0),
            block_size=getattr(self.pool, "block_size", 0),
            tp=1,  # global K/V bytes; the tp shard factor applies below
            dtype=self._kv_quant,  # None = native itemsize (4, CPU proxy)
        )
        kv_shard = kv_heads_shard(cfg.num_heads, tp_size)
        s = self.num_slots
        width = {
            "prefill": self.prefill_chunk, "decode": 1,
            "verify": self.spec_k + 1,
        }[program]
        table = 4 * s * self.pool.blocks_per_slot if self.paged else 0
        operands = {
            # tokens + positions (+ last_idx / draft_len) + rng, all i32.
            "prefill": 4 * s * self.prefill_chunk + 4 * s + 4 * s,
            "decode": 4 * s + 4 * s,
            "verify": 4 * s * (self.spec_k + 1) + 4 * s + 4 * s,
        }[program] + table + 8
        activations = serve_activation_estimate(
            num_slots=s, width=width, hidden=cfg.hidden_dim,
            num_heads=cfg.num_heads, vocab=cfg.vocab_size,
            mask_len=self.pool.mask_len, cache_bytes=cache_dev,
            head_dim=head_dim, kv_quant=self._kv_quant is not None,
        )
        arguments = params_dev + cache_dev + operands
        return {
            "params": params_dev,
            "kv_cache": cache_dev,
            # Closed-form K/V bytes per shard plus the tree's replicated
            # index/control leaves: equals ``kv_cache`` exactly when the
            # pool's compiled shapes match the config's closed form.
            "kv_cache_model": kv_model // kv_shard
            + (cache_dev - int(kv_leaf_bytes) // kv_shard),
            "operands": operands,
            "activation_estimate": activations,
            "arguments": arguments,
            "aliased": cache_dev,
            "total": arguments + activations,
        }

    def reset(self) -> None:
        """Drop all in-flight requests, the prefix cache, the drafter
        index, and the sampling rng (bench sweeps reuse one engine — and
        its compiled executables — across runs; a leg must see the SAME
        engine state regardless of what ran before it).

        Order-independence details (pinned by tests/test_serve_router.py):
        the per-slot spec-decode backoff state (``spec_fail``/``spec_skip``)
        dies with ``_slots``; the rng rewinds to the construction seed so
        sampled legs replay identically; and the shared ``NgramIndex`` is
        cleared IN PLACE, never replaced — the router shares one index
        object across every replica's drafter, and swapping in a fresh one
        here would fork that sharing."""
        self._slots = [None] * self.num_slots
        self.slot_cap = None
        self.pool.reset()
        self.prefill_tokens_computed = 0
        self.prefill_tokens_offered = 0
        self.decode_ticks = 0
        self.decode_slot_ticks = 0
        self.decode_tokens = 0
        self.spec_drafted_tokens = 0
        self.spec_accepted_tokens = 0
        self._rng = jax.random.PRNGKey(self._seed)
        if self._replicated is not None:
            self._rng = jax.device_put(self._rng, self._replicated)
        if self.drafter is not None and self.drafter.index is not None:
            self.drafter.index.clear()
