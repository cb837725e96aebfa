"""GenerationEngine: jitted autoregressive decode over a paged KV cache.

One engine serves one registry LM (kind="lm") at a FIXED batch shape: every
decode step runs all ``max_slots`` rows whether or not a request occupies
them — that is what makes continuous batching recompile-free (one jit cache
entry across the whole serving lifetime; tests pin ``_cache_size() == 1``)
and what lets slots join/leave between steps without reshaping anything.

Two jitted programs, both built ONCE in ``__init__`` (never per request —
lint J2's regression class):

- ``_prefill``: one slot's padded prompt ([1, max_prefill]) through the
  full causal forward; K/V for real positions are scattered into the
  slot's pages (padding lands on the scratch page), and the last real
  position's logits seed the first sampled token. Exact because padding
  sits at the END under a causal mask: no real position can attend to it.
- ``_step``: one token per slot ([max_slots]) — embed + per-layer
  (write K/V into pages at position ``lengths[s]``, ragged paged attention
  over ``lengths[s]+1`` cached positions, MLP) + head + sampling (greedy
  at temperature 0, categorical otherwise, per-slot temperature). The
  page pools are DONATED through both programs, so exactly one generation
  of the cache exists in device memory.

Sampling is **per-slot position-seeded**: the categorical draw for the
token at sequence position ``p`` of a request seeded ``s`` uses the key
``fold_in(fold_in(PRNGKey(0), s), p)`` — a pure function of (seed,
position), independent of batch composition, step count, or which slot row
the request occupies. That is what makes a migrated stream token-identical
to its unkilled reference (docs/GENERATE.md §Migration): re-prefilling
``prompt + delivered_prefix`` on another member with the same seed resumes
the identical random sequence at the identical position, so the
continuation equals the uninterrupted run token for token.

The forward math mirrors ``parallel.sp_transformer.SPTransformerLM``
parameter-for-parameter (same trees, flax LayerNorm/Dense/gelu semantics,
dense_attention's f32 score discipline), so decode logits match the full-
sequence ``lm.apply`` within float tolerance — the paged-KV correctness
pin. ``cache="contiguous"`` swaps the paged gather for a dense per-slot
cache with identical math: the parity reference for the paged path, and
the baseline the 2x continuous-batching pin measures against.
"""

from __future__ import annotations

from typing import Any

import numpy as np

from dmlc_tpu.generate.kvcache import SCRATCH_PAGE, PagedKVCache
from dmlc_tpu.utils.tracing import tracer


# ---------------------------------------------------------------------------
# flax-parity primitives (pure functions over the module's param tree)
# ---------------------------------------------------------------------------


def _layer_norm(x: Any, p: Any) -> Any:
    # flax.linen.LayerNorm semantics: population moments over the last
    # axis, epsilon 1e-6, learned scale + bias.
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + 1e-6) * p["scale"] + p["bias"]


def _dense(x: Any, p: Any) -> Any:
    return x @ p["kernel"] + p["bias"]


def _split_heads(x: Any, num_heads: int) -> Any:
    # [..., D] -> [..., H, Dh]
    return x.reshape(*x.shape[:-1], num_heads, x.shape[-1] // num_heads)


class GenerationEngine:
    """Continuous-batching decode driver for one registry LM.

    Host-side state (lengths, active flags, temperatures, the page table)
    is NumPy; device state is the param tree and the KV pools. Mutating
    methods (join/step/release) must be serialized by the caller — the
    SlotScheduler's decode thread is the only writer in production;
    ``reserve``/``release_reservation`` are thread-safe (the allocator has
    its own lock) so admission can run on RPC threads.
    """

    def __init__(
        self,
        model_name: str,
        *,
        variables: Any = None,
        dtype: Any = None,
        max_slots: int = 8,
        page_size: int = 16,
        num_pages: int = 128,
        max_prefill: int = 64,
        cache: str = "paged",
        use_pallas: bool | None = None,
        return_logits: bool = False,
        seed: int = 0,
        device_work: Any = None,
    ) -> None:
        import jax
        import jax.numpy as jnp

        from dmlc_tpu.models.registry import get_model

        # Device-plane telemetry hook (cluster/devicemon.py): called with
        # (model, tokens, seconds) per decode step so the node's
        # DeviceMonitor can track achieved FLOP/s vs roofline. None = off.
        self.device_work = device_work

        if cache not in ("paged", "contiguous"):
            raise ValueError(f"cache must be 'paged' or 'contiguous', got {cache!r}")
        spec = get_model(model_name)
        if spec.kind != "lm":
            raise ValueError(f"{model_name!r} is not a language model (kind={spec.kind})")
        self.spec = spec
        self.model_name = spec.name
        self.dtype = dtype if dtype is not None else jnp.float32
        module = spec.module(dtype=self.dtype)
        if variables is None:
            # Seed init: generation is servable with no published weights,
            # exactly like the predict path before `train`.
            _, variables = spec.init_params(
                jax.random.PRNGKey(0), dtype=self.dtype, batch_size=1
            )
        self._variables = jax.device_put(variables)
        self.vocab = int(module.vocab)
        self.num_layers = int(module.num_layers)
        self.num_heads = int(module.num_heads)
        self.hidden = int(module.hidden)
        self.head_dim = self.hidden // self.num_heads
        self.max_len = int(module.max_len)
        self.max_slots = int(max_slots)
        self.max_prefill = min(int(max_prefill), self.max_len)
        if use_pallas is None:
            use_pallas = jax.default_backend() == "tpu"
        self.use_pallas = bool(use_pallas)
        self.cache_mode = cache
        self.return_logits = bool(return_logits)

        max_pages_per_slot = -(-self.max_len // int(page_size))
        if cache == "paged":
            self.cache = PagedKVCache(
                num_layers=self.num_layers,
                num_pages=num_pages,
                page_size=page_size,
                num_heads=self.num_heads,
                head_dim=self.head_dim,
                max_slots=self.max_slots,
                max_pages_per_slot=max_pages_per_slot,
                dtype=self.dtype,
            )
            self.max_tokens = min(self.max_len, self.cache.max_tokens_per_slot)
            self._k_state = self.cache.k_pages
            self._v_state = self.cache.v_pages
        else:
            self.cache = None
            self.max_tokens = self.max_len
            shape = (
                self.num_layers, self.max_slots, self.max_tokens,
                self.num_heads, self.head_dim,
            )
            self._k_state = jnp.zeros(shape, self.dtype)
            self._v_state = jnp.zeros(shape, self.dtype)

        # Host-side slot registers (fixed batch shape).
        self.lengths = np.zeros(self.max_slots, np.int32)
        self.active = np.zeros(self.max_slots, bool)
        self.temps = np.zeros(self.max_slots, np.float32)
        self.steps = 0
        self.tokens_out = 0
        self.last_tokens = np.zeros(self.max_slots, np.int32)
        self.last_logits: np.ndarray | None = None
        # Per-slot sampling seeds (position-seeded RNG, module docstring).
        # Default seeds derive deterministically from the engine seed and a
        # join counter; a caller-supplied seed (the router's migration path)
        # overrides so a resumed stream replays the same random sequence.
        self.seeds = np.zeros(self.max_slots, np.uint32)
        self._base_seed = int(seed)
        self._joins = 0

        # The two compiled programs — built exactly once (J2/H1 contract),
        # census-wrapped so a steady-state recompile of either is a labeled
        # flight alert (cluster/devicemon.py; the wrapper passes
        # ``_cache_size`` through, so the ==1 invariant pins unchanged).
        from dmlc_tpu.cluster.devicemon import CensusedJit

        self._step = CensusedJit(f"gen/{self.model_name}/step", self._build_step())
        self._prefill = CensusedJit(
            f"gen/{self.model_name}/prefill", self._build_prefill()
        )

    # ---- forward math ---------------------------------------------------

    def _params(self, variables: Any) -> Any:
        return variables["params"]

    def _attend(self, q: Any, k_state: Any, v_state: Any, layer: int,
                page_table: Any, kv_lengths: Any, slots: Any = None) -> Any:
        """Per-layer decode attention: paged gather + ragged mask, or the
        contiguous per-slot view. q: [B, H, Dh] -> [B, H, Dh]."""
        from dmlc_tpu.ops.ragged_decode import (
            gather_kv_pages,
            ragged_decode_attention,
        )

        if self.cache_mode == "paged":
            k = gather_kv_pages(k_state[layer], page_table, use_pallas=self.use_pallas)
            v = gather_kv_pages(v_state[layer], page_table, use_pallas=self.use_pallas)
        else:
            k, v = k_state[layer], v_state[layer]  # [B, S_max, H, Dh]
        return ragged_decode_attention(q, k, v, kv_lengths)

    def _build_step(self) -> Any:
        import jax
        import jax.numpy as jnp

        num_heads = self.num_heads
        page_size = self.cache.page_size if self.cache_mode == "paged" else 0
        num_layers = self.num_layers
        return_logits = self.return_logits

        def step(variables: Any, k_state: Any, v_state: Any, tokens: Any,
                 lengths: Any, active: Any, page_table: Any, seeds: Any,
                 temps: Any) -> Any:
            p = self._params(variables)
            pos = jnp.minimum(lengths, self.max_len - 1)
            x = p["embed"]["embedding"][tokens] + p["pos_embed"]["embedding"][pos]
            x = x.astype(self.dtype)
            if self.cache_mode == "paged":
                # Destination of this step's K/V: the page covering position
                # ``lengths[s]`` — inactive rows write into scratch page 0.
                page_idx = jnp.take_along_axis(
                    page_table, (lengths // page_size)[:, None], axis=1
                )[:, 0]
                dest_page = jnp.where(active, page_idx, SCRATCH_PAGE)
                dest_off = lengths % page_size
            kv_lengths = jnp.maximum(lengths + 1, 1)
            batch = jnp.arange(tokens.shape[0])
            for layer in range(num_layers):
                blk = p[f"block{layer}"]
                h = _layer_norm(x, blk["ln1"])
                q = _split_heads(_dense(h, blk["attn"]["query"]), num_heads)
                k = _split_heads(_dense(h, blk["attn"]["key"]), num_heads)
                v = _split_heads(_dense(h, blk["attn"]["value"]), num_heads)
                if self.cache_mode == "paged":
                    k_state = k_state.at[layer, dest_page, dest_off].set(k)
                    v_state = v_state.at[layer, dest_page, dest_off].set(v)
                else:
                    k_state = k_state.at[layer, batch, lengths].set(k)
                    v_state = v_state.at[layer, batch, lengths].set(v)
                att = self._attend(q, k_state, v_state, layer, page_table, kv_lengths)
                x = x + _dense(att.reshape(att.shape[0], -1), blk["attn"]["out"])
                h2 = _layer_norm(x, blk["ln2"])
                h2 = jax.nn.gelu(_dense(h2, blk["mlp_in"]))
                x = x + _dense(h2, blk["mlp_out"])
            x = _layer_norm(x, p["ln_f"])
            logits = _dense(x, p["head"]).astype(jnp.float32)  # [B, V]
            # The token sampled here lands at sequence position ``lengths``
            # (pre-increment) — the position the key must be folded on.
            nxt = _sample(logits, seeds, lengths, temps)
            if return_logits:
                return k_state, v_state, nxt, logits
            return k_state, v_state, nxt

        return jax.jit(step, donate_argnums=(1, 2))

    def _build_prefill(self) -> Any:
        import jax
        import jax.numpy as jnp

        from dmlc_tpu.parallel.ring_attention import dense_attention

        num_heads = self.num_heads
        num_layers = self.num_layers
        page_size = self.cache.page_size if self.cache_mode == "paged" else 0
        s_pad = self.max_prefill

        def prefill(variables: Any, tokens: Any, length: Any, k_state: Any,
                    v_state: Any, dest: Any, seed: Any, temp: Any) -> Any:
            """tokens: [1, s_pad]; length: [] int32 (real prompt length);
            dest: page row [max_pages_per_slot] (paged) or slot index []
            (contiguous)."""
            p = self._params(variables)
            x = p["embed"]["embedding"][tokens] + p["pos_embed"]["embedding"][
                jnp.arange(s_pad)
            ][None, :]
            x = x.astype(self.dtype)
            seq = jnp.arange(s_pad)
            if self.cache_mode == "paged":
                dest_page = jnp.where(seq < length, dest[seq // page_size], SCRATCH_PAGE)
                dest_off = seq % page_size
            for layer in range(num_layers):
                blk = p[f"block{layer}"]
                h = _layer_norm(x, blk["ln1"])
                q = _split_heads(_dense(h, blk["attn"]["query"]), num_heads)
                k = _split_heads(_dense(h, blk["attn"]["key"]), num_heads)
                v = _split_heads(_dense(h, blk["attn"]["value"]), num_heads)
                if self.cache_mode == "paged":
                    k_state = k_state.at[layer, dest_page, dest_off].set(k[0])
                    v_state = v_state.at[layer, dest_page, dest_off].set(v[0])
                else:
                    # Positions past ``length`` are scratch rows the ragged
                    # mask never exposes; later decode steps overwrite them.
                    k_state = k_state.at[layer, dest, :s_pad].set(k[0])
                    v_state = v_state.at[layer, dest, :s_pad].set(v[0])
                qh = q.transpose(0, 2, 1, 3)  # [1, H, S, Dh]
                att = dense_attention(
                    qh, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), causal=True
                ).transpose(0, 2, 1, 3)
                x = x + _dense(att.reshape(1, s_pad, -1), blk["attn"]["out"])
                h2 = _layer_norm(x, blk["ln2"])
                h2 = jax.nn.gelu(_dense(h2, blk["mlp_in"]))
                x = x + _dense(h2, blk["mlp_out"])
            x = _layer_norm(x, p["ln_f"])
            logits = _dense(x, p["head"]).astype(jnp.float32)  # [1, S, V]
            last = jnp.take(logits[0], length - 1, axis=0)     # [V]
            # First sampled token comes from position ``length - 1`` — the
            # same position a resumed prefill of prompt+prefix re-samples.
            nxt = _sample(
                last[None],
                jnp.reshape(seed, (1,)),
                jnp.reshape(length - 1, (1,)),
                temp[None],
            )[0]
            return k_state, v_state, nxt, last

        return jax.jit(prefill, donate_argnums=(3, 4))

    # ---- admission (thread-safe) ----------------------------------------

    def reserve(self, prompt_len: int) -> list[int]:
        """Reserve pages for a prompt plus its first generated token.
        Raises PagePoolExhausted — the submit-time shed signal. Contiguous
        mode has nothing to reserve (capacity is the slot row itself)."""
        if self.cache_mode != "paged":
            return []
        need = self.cache.allocator.pages_for(int(prompt_len) + 1)
        return self.cache.allocator.alloc(need)

    def release_reservation(self, pages: list[int]) -> None:
        if self.cache_mode == "paged" and pages:
            self.cache.allocator.free(pages)

    # ---- slot lifecycle (decode-thread only) -----------------------------

    def warmup(self) -> None:
        """Compile both programs NOW by running a one-token prompt through
        prefill and one decode step in slot 0, then retiring it — so a
        kernel the compiler refuses (or an OOM) fails here, at node start,
        not inside the first request. Call before the engine serves. Leaves
        no trace: slot, pages and counters are as before (the K/V it wrote
        sits in freed pages, which the next prefill overwrites), and the
        compile-inflated step is kept out of the MFU window."""
        if self.active.any():
            raise RuntimeError("warmup on an engine that is already serving")
        counters = (self.steps, self.tokens_out, self._joins)
        device_work, self.device_work = self.device_work, None
        try:
            self.join(0, [0])
            self.ensure_capacity(0)
            self.step()
            self.release(0)
        finally:
            self.device_work = device_work
        self.steps, self.tokens_out, self._joins = counters

    def free_slots(self) -> list[int]:
        return [s for s in range(self.max_slots) if not self.active[s]]

    def join(self, slot: int, prompt: Any, *, temperature: float = 0.0,
             pages: list[int] | None = None, seed: int | None = None) -> int:
        """Prefill ``prompt`` into ``slot`` and return the first sampled
        token. ``pages`` is the submit-time reservation (paged mode).
        ``seed`` keys the position-seeded sampling RNG; passing the same
        seed with ``prompt + delivered_prefix`` resumes a migrated stream
        token-identically (module docstring)."""
        import jax.numpy as jnp

        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError("prompt must be a non-empty 1-D token sequence")
        if prompt.size > self.max_prefill:
            raise ValueError(
                f"prompt of {prompt.size} tokens exceeds max_prefill="
                f"{self.max_prefill}"
            )
        if self.active[slot]:
            raise ValueError(f"slot {slot} is already active")
        if self.cache_mode == "paged":
            if pages is None:
                pages = self.reserve(prompt.size)
            self.cache.bind(slot, pages)
            dest = jnp.asarray(self.cache.page_table[slot], jnp.int32)
        else:
            dest = jnp.int32(slot)
        padded = np.zeros(self.max_prefill, np.int32)
        padded[: prompt.size] = prompt
        if seed is None:
            seed = (self._base_seed * 1_000_003 + self._joins) % (1 << 31)
        self._joins += 1
        seed = int(seed) & 0xFFFFFFFF
        k_state, v_state, nxt, last = self._prefill(
            self._variables,
            jnp.asarray(padded[None]),
            jnp.int32(prompt.size),
            self._k_state,
            self._v_state,
            dest,
            jnp.uint32(seed),
            jnp.float32(temperature),
        )
        self._set_state(k_state, v_state)
        # The one call of join that blocks on the device; what is left of
        # the caller's gen/prefill span is the host's part.
        with tracer.span("gen/prefill_sync", cpu=True):
            first = int(nxt)
        self.lengths[slot] = prompt.size
        self.active[slot] = True
        self.temps[slot] = float(temperature)
        self.seeds[slot] = seed
        self.last_tokens[slot] = first
        self.tokens_out += 1
        return first

    def ensure_capacity(self, slot: int) -> None:
        """Grow the slot's page run if the NEXT step's write would cross a
        page boundary. Raises PagePoolExhausted (eviction policy is the
        scheduler's call, not the engine's)."""
        if self.cache_mode != "paged":
            return
        if not self.cache.capacity_ok(slot, int(self.lengths[slot]) + 1):
            self.cache.grow(slot)

    def step(self) -> np.ndarray:
        """One decode step over every active slot (fixed batch shape).
        Appends the previous sampled token to each slot's cache and samples
        the next; returns the sampled token per slot ([max_slots], only
        active rows meaningful). Host state advances for active slots."""
        import time

        import jax.numpy as jnp

        t0 = time.perf_counter()
        table = (
            jnp.asarray(self.cache.page_table)
            if self.cache_mode == "paged"
            else jnp.zeros((self.max_slots, 1), jnp.int32)
        )
        out = self._step(
            self._variables,
            self._k_state,
            self._v_state,
            jnp.asarray(self.last_tokens),
            jnp.asarray(self.lengths),
            jnp.asarray(self.active),
            table,
            jnp.asarray(self.seeds),
            jnp.asarray(self.temps),
        )
        if self.return_logits:
            k_state, v_state, nxt, logits = out
        else:
            k_state, v_state, nxt = out
        self._set_state(k_state, v_state)
        # The one place step blocks on the device; what is left of the
        # caller's gen/step span is the host's part (uploads, dispatch,
        # bookkeeping).
        with tracer.span("gen/step_sync", cpu=True):
            if self.return_logits:
                self.last_logits = np.asarray(logits)
            tokens = np.asarray(nxt)
        n_active = int(self.active.sum())
        self.lengths[self.active] += 1
        self.last_tokens[self.active] = tokens[self.active]
        self.steps += 1
        self.tokens_out += n_active
        if self.device_work is not None and n_active > 0:
            # np.asarray(nxt) above materialized the step's results, so
            # this wall is the step's real device+host latency.
            self.device_work(self.model_name, n_active, time.perf_counter() - t0)
        return tokens

    def release(self, slot: int) -> list[int]:
        """Slot exit: recycle its pages, reset its registers. Returns the
        freed page ids."""
        self.active[slot] = False
        self.lengths[slot] = 0
        self.temps[slot] = 0.0
        self.seeds[slot] = 0
        self.last_tokens[slot] = 0
        if self.cache_mode == "paged":
            return self.cache.release(slot)
        return []

    def _set_state(self, k_state: Any, v_state: Any) -> None:
        self._k_state = k_state
        self._v_state = v_state
        if self.cache_mode == "paged":
            self.cache.k_pages = k_state
            self.cache.v_pages = v_state

    # ---- observability / weights ----------------------------------------

    @property
    def slots_active(self) -> int:
        return int(self.active.sum())

    @property
    def pages_free(self) -> int:
        return self.cache.pages_free if self.cache_mode == "paged" else 0

    def resident_bytes(self) -> int:
        """Analytic device residency of this engine: weights pytree + both
        KV pools (paged or contiguous) — the per-model attribution behind
        the ``resident_bytes_<model>`` gauge (docs/OBSERVABILITY.md §8)."""
        from dmlc_tpu.cluster.devicemon import pytree_nbytes

        return (
            pytree_nbytes(self._variables)
            + pytree_nbytes(self._k_state)
            + pytree_nbytes(self._v_state)
        )

    def jit_cache_sizes(self) -> dict[str, int]:
        """Compiled-entry counts for the two programs — the recompile-free
        invariant's measurement (must stay 1 apiece at any request mix)."""
        return {
            "step": self._step._cache_size(),
            "prefill": self._prefill._cache_size(),
        }

    def load_variables(self, variables: Any) -> None:
        """Hot-swap weights (the `train` verb's member side). Same shapes
        by construction (ModelLoader validated against the registry
        template), so the jit cache entries are reused, not recompiled."""
        import jax

        self._variables = jax.device_put(variables)

    def summary(self) -> dict[str, Any]:
        out: dict[str, Any] = {
            "model": self.model_name,
            "cache": self.cache_mode,
            "max_slots": self.max_slots,
            "slots_active": self.slots_active,
            "steps": self.steps,
            "tokens_out": self.tokens_out,
            "jit_entries": self.jit_cache_sizes(),
        }
        if self.cache_mode == "paged":
            out["pages"] = self.cache.allocator.summary()
        return out


def _sample(logits: Any, seeds: Any, positions: Any, temps: Any) -> Any:
    """Greedy at temperature <= 0, position-seeded categorical otherwise —
    per row. logits: [B, V] f32; seeds: [B] u32; positions: [B] i32 (the
    sequence position each row's token lands at); temps: [B] f32. The key
    ``fold_in(fold_in(PRNGKey(0), seed), position)`` depends only on the
    (seed, position) pair, never on batch composition — the property the
    migration token-identity guarantee rests on (module docstring)."""
    import jax
    import jax.numpy as jnp

    greedy = jnp.argmax(logits, axis=-1)
    temp_safe = jnp.maximum(temps, 1e-6)[:, None]
    base = jax.random.PRNGKey(0)
    keys = jax.vmap(
        lambda s, p: jax.random.fold_in(jax.random.fold_in(base, s), p)
    )(seeds, positions)
    sampled = jax.vmap(
        lambda k, row: jax.random.categorical(k, row, axis=-1)
    )(keys, logits / temp_safe)
    return jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)
