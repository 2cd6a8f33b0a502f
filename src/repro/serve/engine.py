"""Per-step continuous-batching serving engine with chunked prefill.

One fixed-shape batched decode state (the slot table) runs ONE unified step
per iteration: every in-flight prefill advances by exactly one fixed-size
chunk (``prefill_chunk`` tokens grafted into its reserved slot's cache by
state surgery), then one jitted decode step runs over every occupied slot.
Long prompts therefore never stall token generation for running slots — a
1000-token prompt is ~(1000/chunk) interleaved chunk steps, with decode
emitting a token for every running request in each of them.

Prompts are padded to UPD-declared length buckets before admission
(``BucketPolicy``), and every bucket is an exact multiple of the chunk size,
so the engine only ever runs shapes it has compiled before: ONE prefill-chunk
shape (plus one first-chunk shape for families with per-request media) and
ONE decode shape — bounded by len(buckets) + 1 per family. Bucket padding is
mathematically exact: pad rows' state updates are skipped (``n_real``
masking in ``Model.prefill_chunk``) and the first token is sampled at the
last REAL row, so chunked+bucketed prefill is token-for-token identical to
whole-prompt prefill.

Requests arrive asynchronously: ``submit()`` (or a preset ``arrival_s`` on
the request) makes a request visible to admission only once the engine clock
reaches its arrival — TTFT and SLA accounting are measured from that arrival,
and shared-step wall time is attributed proportionally to prefill vs decode
tokens so a neighbour's chunk work never inflates a request's decode-t/s.

Speculative decoding (``speculation=SpeculationConfig(...)``) replaces the
per-step decode with a draft/verify loop when the UPD cost channel says it
pays: a drafter proposes up to k tokens per slot, ONE batched ragged verify
step (``Model.verify_step`` over the ``attention_verify`` primitive) scores
every slot's span at its own position, and each slot commits its longest
accepted prefix plus one corrected token. Depth k is per-slot per-step
(``SpeculationPolicy``: acceptance EMA vs drafter + verify roofline cost)
and k = 0 runs the ORIGINAL decode path verbatim — same jitted function,
same sampler, same key draws — so disabled/unprofitable speculation is
token-for-token identical to the plain engine.

Metrics per request: TTFT, prefill_s/decode_s attribution, decode tokens/s
(counting ONLY target-emitted tokens — accepted + corrected, never rejected
drafts), end-to-end latency, SLA hit, bucket; per engine run: real-token
throughput (padded/idle slots never counted), steady-state padded-slot steps
(0 == true continuous batching), TTFT percentiles split by bucket, slot-reuse
counts, the per-step log (chunks run / tokens decoded / emitted), the
admission log, every refusal with its cost-model reason, and — with
speculation on — the ``spec`` block (accepted rate, mean accepted span,
steps per emitted token, split by bucket).
"""

from __future__ import annotations

import time
from dataclasses import asdict, dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.dist import sharding as dist_sharding
from repro.nn.model import build_model

from .paging import PagedConfig, PagedKVStore, prefix_key, selected_page_size
from .scheduler import (BucketPolicy, CostModelAdmission, PagedAdmission,
                        Refusal, Request, Scheduler)
from .slots import PagesExhausted, assert_span_fits, validate_donor
from .spec import (SpeculationConfig, SpeculationPolicy, accept_span,
                   build_drafter, upd_verify_defaults)


@dataclass(frozen=True)
class SamplingConfig:
    """temperature <= 0 -> greedy argmax; top_k 0 -> no truncation."""

    temperature: float = 0.0
    top_k: int = 0


@dataclass
class _PrefillTask:
    """Host-side tracking of one request's chunk schedule.

    The in-flight prefill lives in a batch-1 DONOR state outside the slot
    table, not in the reserved slot itself: the batched decode step runs
    over the FULL table every iteration, and a reserved slot's lane would be
    advanced with a garbage token between chunk steps (clobbering K/V rows
    at its stale position, corrupting recurrent state). The donor is grafted
    into the slot once, at completion."""

    req: Request
    slot: int
    padded: np.ndarray          # (1, bucket) prompt padded to its bucket
    n_chunks: int
    donor: object               # batch-1 decode-state pytree being filled
    chunk_idx: int = 0
    fill: int = 0               # REAL rows in the donor's cache (incl. prefix)
    first_logits: np.ndarray | None = None   # logits at the last real row
    prefill_s: float = 0.0
    # paged mode (slot == -1: no lane is reserved; the request activates
    # into a free lane at completion or parks resident in pages)
    share_key: str | None = None    # prefix-store content address
    share_rows: int = 0             # aligned share-boundary cache rows
    publish: bool = False           # miss: this task publishes the prefix
    boundary_tail: dict | None = None   # tail snapshot AT the boundary


class ServeEngine:
    def __init__(self, cfg, *, batch: int, max_len: int,
                 sampling: SamplingConfig | None = None, seed: int = 0,
                 enc_len: int | None = None, admission: bool = True,
                 prefill_chunk: int | None = None,
                 buckets: tuple[int, ...] | None = None,
                 speculation: SpeculationConfig | None = None,
                 paged: PagedConfig | None = None,
                 mesh=None):
        if cfg.family == "audio" and enc_len is None:
            raise ValueError("audio family: pass enc_len (the fixed encoder "
                             "length every request's frames are sized to)")
        self.cfg = cfg
        self.batch = batch
        self.max_len = max_len
        self.enc_len = enc_len
        self.sampling = sampling or SamplingConfig()
        self.model = build_model(cfg)
        self.params = self.model.init(jax.random.PRNGKey(seed))
        # -- mesh-sharded serving (repro.dist rules over a jax mesh) ---------
        # params shard row/col-TP with the output-projection flip; slot-table
        # and page-pool state shard batch-on-data / sequence-on-model. Every
        # jitted step pins its returned state to those SAME rules
        # (with_sharding_constraint), so inputs and outputs agree and
        # steady-state steps run with zero resharding — asserted by the
        # ``reshard_events`` counter the report carries.
        self.mesh = mesh
        self._reshard_events = 0
        if mesh is not None:
            # donate: the unsharded copy on the default device is freed now
            self.params = jax.device_put(
                self.params, dist_sharding.param_shardings(mesh, self.params),
                donate=True)
        # the slot cache is filled to prompt_len + decode_prefix (vlm vision
        # rows), and decode must write AFTER it
        self._prefix = cfg.decode_prefix
        # chunk size + admissible padded prompt lengths are UPD data (the
        # serve: block on attention_prefill_chunk); buckets that cannot fit
        # the slot table (prefix + bucket + 1 gen token) are dropped, and an
        # engine with none left falls back to the largest chunk multiple
        # that fits, so tiny test tables still serve
        base = BucketPolicy.from_upd(chunk=prefill_chunk, buckets=buckets)
        chunk = base.chunk
        fit = tuple(b for b in base.buckets
                    if self._prefix + b + 1 <= max_len)
        if not fit:
            largest = ((max_len - self._prefix - 1) // chunk) * chunk
            if largest < chunk:
                raise ValueError(
                    f"max_len={max_len} leaves no room for a single "
                    f"prefill chunk of {chunk}")
            fit = (largest,)
        self.policy = BucketPolicy(fit, chunk)
        # -- speculative decoding depth (needed before the paged store: the
        # slot table carries k_max scratch rows) -----------------------------
        # The verify span writes k_max+1 cache rows at each slot's fill; a
        # slot whose window is smaller than the step's global K would have
        # rows from NEIGHBOURS' depth written past its own budget, so the
        # slot table (and every donor) carries k_max scratch rows of
        # headroom beyond max_len — dynamic_update_slice clamping near the
        # boundary would otherwise silently corrupt the last real rows.
        self.spec = speculation
        self._k_max = 0
        if speculation is not None:
            self._k_max = speculation.k_max if speculation.k_max is not None \
                else upd_verify_defaults()["k_max"]
        self._state_len = max_len + self._k_max
        # family-declared per-leaf axis contracts drive the state sharding
        # rules: state_page_axes names the TRUE token axis of each leaf (None
        # = fixed-size recurrent tail — sharding one of its feature axes on
        # ``model`` would reassociate the reductions that consume it and
        # break token-for-token equivalence), state_batch_axes the request
        # axis. Families without the contracts fall back to the shape
        # heuristic in dist.sharding.
        self._state_token_axes = None
        self._state_batch_axes = None
        if mesh is not None and self.model.state_page_axes is not None:
            shapes = jax.eval_shape(
                lambda: self.model.init_decode_state(
                    1, self._state_len, enc_len=self.enc_len))
            if isinstance(shapes, dict):
                self._state_token_axes = self.model.state_page_axes(shapes)
                if self.model.state_batch_axes is not None:
                    self._state_batch_axes = self.model.state_batch_axes(shapes)
        # -- paged slot memory (block-table residency under the lanes) -------
        self.paged = paged
        self._store: PagedKVStore | None = None
        self._seed = seed
        self._max_inflight = 0
        self._parked: dict[str, dict] = {}      # rid -> resume info (FIFO)
        self._resumed: dict[str, dict] = {}     # rid -> preemption stash
        self._inflight_keys: dict[str, str] = {}  # share key -> publisher rid
        self._act_stamp: dict[int, int] = {}    # slot -> activation seq
        self._act_seq = 0
        self._preempt_count = 0
        if paged is not None:
            if self.model.state_page_axes is None:
                raise ValueError(f"family {cfg.family!r} does not declare "
                                 "state_page_axes (paged serving needs the "
                                 "per-leaf token-axis contract)")
            donor_shapes = jax.eval_shape(
                lambda: self.model.init_decode_state(
                    1, self._state_len, enc_len=self.enc_len))
            page_axes = self.model.state_page_axes(donor_shapes)
            psize = paged.page_size or selected_page_size()
            if paged.hbm_budget_bytes is not None:
                self._store = PagedKVStore(
                    donor_shapes, page_axes, page_size=psize,
                    hbm_budget_bytes=paged.hbm_budget_bytes, int8=paged.int8,
                    fused=paged.fused)
            else:
                # default budget: pages for 2x the lane count at worst-case
                # length — out of the box, paged strictly dominates the
                # contiguous table and never preempts a lane-bound load
                probe = PagedKVStore(donor_shapes, page_axes,
                                     page_size=psize, n_pages=1,
                                     int8=paged.int8)
                self._store = PagedKVStore(
                    donor_shapes, page_axes, page_size=psize,
                    n_pages=2 * batch * max(probe.pages_for_rows(max_len), 1),
                    int8=paged.int8, fused=paged.fused)
            self._max_inflight = paged.max_inflight_prefills or 2 * batch
            self._page_axes = page_axes
        # FUSED paged decode: KV-family slots decode/verify DIRECTLY against
        # the block-table page pools (attention_decode_paged /
        # attention_verify_paged) — no page->lane gather on the steady-state
        # path. The store downgrades fused for families with no paged leaves
        # (rwkv), and a family without the paged step contract falls back to
        # lane activation the same way.
        self._fused = bool(self._store is not None and self._store.fused
                           and self.model.decode_step_paged is not None)
        if self._store is not None and self._store.fused and not self._fused:
            # paged leaves but no fused contract: rebuild flat (lane mode)
            self._store = PagedKVStore(
                donor_shapes, page_axes, page_size=self._store.page,
                n_pages=self._store.n_pages, int8=paged.int8)
        self._table_width = 0
        if self._fused:
            self._table_width = -(-self._state_len // self._store.page)
        # page pools shard like the slot state they mirror: the token axis
        # was split into (n_pages, page), so the PAGE axis takes the model
        # entry the sequence dim would have (divisibility-guarded), and the
        # engine re-pins after any host-path pool mutation so fused steps
        # always see the same input shardings they compiled against
        self._pool_shardings: dict | None = None
        if mesh is not None and self._store is not None:
            self._pool_shardings = self._pool_sharding_rules()
            for n in self._store.pools:
                self._store.pools[n] = jax.device_put(
                    self._store.pools[n], self._pool_shardings[n],
                    donate=True)
            for n in self._store.scale_pools:
                key = f"{n}__scale"
                self._store.scale_pools[n] = jax.device_put(
                    self._store.scale_pools[n], self._pool_shardings[key],
                    donate=True)
        # fused-path counters for report["paged"]
        self._lane_activations = 0      # full page->lane gathers (fallback)
        self._tail_restores = 0         # fused activations (tails only)
        self._gather_bytes_eliminated = 0
        if not admission:
            self.cost_model = None
        elif self._store is not None:
            self.cost_model = PagedAdmission(cfg, batch, max_len,
                                             budget=self._store,
                                             enc_len=enc_len,
                                             policy=self.policy, mesh=mesh)
        else:
            self.cost_model = CostModelAdmission(cfg, batch, max_len,
                                                 enc_len=enc_len,
                                                 policy=self.policy,
                                                 mesh=mesh)
        # -- speculative decoding (draft/verify over the slot table) ---------
        self._drafter = None
        self._spec_policy = None
        self._verify = None
        self._commit = None

        # jit wrappers: on a mesh, every compiled step pins its returned
        # state (and pools) to the dist.sharding rules — inputs already
        # carry them, so outputs match inputs and the donated buffers are
        # reused without a single resharding copy in steady state
        def _ls(fn):
            """(logits, state)-returning step."""
            if mesh is None:
                return fn

            def wrapped(params, state, *args):
                logits, st = fn(params, state, *args)
                return logits, self._pin_state(st)
            return wrapped

        def _st(fn):
            """state-returning step (insert/reset/commit)."""
            if mesh is None:
                return fn

            def wrapped(*args):
                return self._pin_state(fn(*args))
            return wrapped

        def _lsp(fn):
            """(logits, state, pools)-returning fused paged step."""
            if mesh is None:
                return fn

            def wrapped(params, state, pools, *args):
                logits, st, pl = fn(params, state, pools, *args)
                return logits, self._pin_state(st), self._pin_pools(pl)
            return wrapped

        def _sp(fn):
            """(state, pools)-returning fused paged commit."""
            if mesh is None:
                return fn

            def wrapped(params, state, pools, *args):
                st, pl = fn(params, state, pools, *args)
                return self._pin_state(st), self._pin_pools(pl)
            return wrapped

        if speculation is not None:
            self._drafter = build_drafter(speculation, cfg, batch=batch,
                                          state_len=self._state_len,
                                          seed=seed + 2)
            pricing = self.cost_model or CostModelAdmission(
                cfg, batch, max_len, enc_len=enc_len, policy=self.policy,
                mesh=mesh)
            if self.cost_model is not None:
                self.cost_model.spec_k = self._k_max
            self._spec_policy = SpeculationPolicy(
                batch, self._k_max, pricing, speculation,
                drafter_cost_s=self._drafter.cost_per_token_s())
            self._verify = jax.jit(_ls(self.model.verify_step),
                                   donate_argnums=(1,))
            if self.model.verify_commit is not None:
                self._commit = jax.jit(_st(self.model.verify_commit),
                                       donate_argnums=(1,))
        # donate the incoming state: it is dead after every call, and without
        # donation each step/insert/reset copies the full multi-layer cache
        self._decode = jax.jit(_ls(self.model.decode_step), donate_argnums=(1,))
        # fused paged steps: the tail state AND the pool dict are donated —
        # the pools are updated in place on device and re-adopted by the
        # store after every call (set_device_pools)
        self._decode_paged = None
        self._verify_paged = None
        self._commit_paged = None
        if self._fused:
            self._decode_paged = jax.jit(_lsp(self.model.decode_step_paged),
                                         donate_argnums=(1, 2))
            if speculation is not None:
                self._verify_paged = jax.jit(_lsp(self.model.verify_step_paged),
                                             donate_argnums=(1, 2))
                if self.model.verify_commit_paged is not None:
                    self._commit_paged = jax.jit(
                        _sp(self.model.verify_commit_paged),
                        donate_argnums=(1, 2))
        self._insert = jax.jit(_st(self.model.insert_slot), donate_argnums=(0,))
        self._reset = jax.jit(_st(self.model.reset_slot), donate_argnums=(0,))
        self._chunk = jax.jit(self._chunk_fn, donate_argnums=(1,))
        self._sample = self._build_sampler()
        self._key = jax.random.PRNGKey(seed + 1)
        self._inbox: list[Request] = []

    # -- helpers --------------------------------------------------------------

    def _chunk_fn(self, params, donor, tokens, pos, n_real, embeds=None):
        """One continuation-prefill chunk into the batch-1 donor state;
        returns the logits row at the chunk's last REAL token and the
        updated donor. ``pos``/``n_real`` are traced — one compiled shape
        covers every fill level and padding amount."""
        logits, donor = self.model.prefill_chunk(
            params, donor, tokens, pos, pos, n_real=n_real, embeds=embeds)
        # vlm/audio first chunk prepends prefix rows: index relative to them
        prefix = logits.shape[1] - tokens.shape[1]
        idx = prefix + jnp.maximum(n_real, 1) - 1
        last = jnp.take(logits, idx, axis=1)                # (1, V)
        return last, donor

    def _build_sampler(self):
        """Per-slot-temperature sampler: ``temps`` (B,) lets greedy and
        sampled requests coexist in one batched step (and in one verify
        span, where logits are (B, SV, V) and every span row samples at its
        slot's temperature). temp <= 0 rows take the argmax; for a uniform
        temperature this reduces exactly to the scalar sampler (same key,
        same categorical draw)."""
        top_k = self.sampling.top_k
        vocab = self.cfg.vocab

        def sample(logits, key, temps):
            # the lm head is padded_vocab wide: never emit a padding id
            keep = jnp.arange(logits.shape[-1]) < vocab
            masked = jnp.where(keep, logits, jnp.full_like(logits, -1e30))
            greedy = jnp.argmax(masked, axis=-1)
            t = temps.reshape((logits.shape[0],) + (1,) * (logits.ndim - 1))
            scaled = masked.astype(jnp.float32) / jnp.maximum(t, 1e-6)
            if top_k:
                kth = jax.lax.top_k(scaled, top_k)[0][..., -1:]
                scaled = jnp.where(scaled < kth, jnp.float32(-1e30), scaled)
            drawn = jax.random.categorical(key, scaled, axis=-1)
            use_draw = temps.reshape(
                (logits.shape[0],) + (1,) * (logits.ndim - 2)) > 0
            return jnp.where(use_draw, drawn, greedy)

        return jax.jit(sample)

    def _slot_temperature(self, req: Request) -> float:
        return self.sampling.temperature if req.temperature is None \
            else float(req.temperature)

    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    # -- mesh helpers ---------------------------------------------------------

    def _state_shardings(self, state):
        """Rule shardings for a slot-table state pytree, steered by the
        family's declared token/batch axis contracts when available."""
        return dist_sharding.state_shardings(
            self.mesh, state, token_axes=self._state_token_axes,
            batch_axes=self._state_batch_axes)

    def _pin_state(self, state):
        """Constrain every state leaf to its ``dist.sharding`` rule — used
        INSIDE the jitted steps so compiled outputs carry exactly the
        shardings the inputs arrived with (the zero-resharding invariant).
        Identity off-mesh."""
        if self.mesh is None:
            return state
        shards = self._state_shardings(state)
        return jax.tree.map(jax.lax.with_sharding_constraint, state, shards)

    def _pin_pools(self, pools: dict) -> dict:
        if self._pool_shardings is None:
            return pools
        return {n: jax.lax.with_sharding_constraint(a, self._pool_shardings[n])
                for n, a in pools.items()}

    def _pool_sharding_rules(self) -> dict:
        """NamedSharding per pool leaf (scale pools as ``{leaf}__scale``):
        the page axis — the split token axis — takes the ``model`` entry the
        sequence dim carries in the slot table, divisibility-guarded."""
        st = self._store
        tp = dist_sharding.tp_size(self.mesh)
        out = {}
        for name, (ax, row_shape, _dt) in st.paged.items():
            page_axis = ax if st.fused else 0
            ndim = len(row_shape) + (2 if st.fused else 1)
            n_along = st.n_pages if st.fused else st.n_pages * st.page
            entries = [None] * ndim
            if tp > 1 and n_along % tp == 0 and n_along >= tp:
                entries[page_axis] = "model"
            spec = PartitionSpec(*entries)
            out[name] = NamedSharding(self.mesh, spec)
            if name in st.scale_pools:
                out[f"{name}__scale"] = NamedSharding(self.mesh, spec)
        return out

    def _sharded_device_pools(self) -> dict:
        """Device pools re-pinned to their rule shardings: host-path writes
        (prefill commit, CoW, spill/rehydrate) run eagerly and may leave a
        pool differently laid out; a no-op when shardings already match, so
        the steady-state decode path never copies."""
        pools = self._store.device_pools()
        if self._pool_shardings is None:
            return pools
        return {n: a if a.sharding == self._pool_shardings[n]
                else jax.device_put(a, self._pool_shardings[n])
                for n, a in pools.items()}

    def _new_donor(self):
        """Fresh batch-1 donor, mesh-placed: created with the SAME rule
        shardings the chunk jit's donated output carries, so every chunk
        call compiles once and reuses the donor buffers."""
        donor = self.model.init_decode_state(1, self._state_len,
                                             enc_len=self.enc_len)
        if self.mesh is not None:
            donor = jax.device_put(donor, self._state_shardings(donor),
                                   donate=True)
        return donor

    def _check_steady_sharding(self, state, pools: dict | None = None):
        """Post-step audit (mesh mode): every state/pool leaf must still
        carry its rule sharding. Any drift is a resharding event — the
        counter lands in report["mesh"]["reshard_events"] and tests assert
        it stays 0."""
        if self.mesh is None:
            return
        expected = self._state_shardings(state)
        leaves = list(zip(jax.tree.leaves(state), jax.tree.leaves(expected)))
        if pools is not None and self._pool_shardings is not None:
            leaves += [(a, self._pool_shardings[n])
                       for n, a in pools.items()]
        for got, want in leaves:
            if not got.sharding.is_equivalent_to(want, got.ndim):
                self._reshard_events += 1

    def _init_state(self):
        # _state_len = max_len + k_max: verify-span slab headroom (see
        # __init__) — admission and the overrun guards still cap real fill
        # at max_len, the scratch rows only ever hold rejected drafts
        state = self.model.init_decode_state(self.batch, self._state_len,
                                             enc_len=self.enc_len)
        if self._fused:
            # fused mode keeps only the TAIL leaves in the slot table: the
            # paged leaves live exclusively in the store's pools and every
            # decode/verify reads them through the block table
            state = {n: state[n] for n, ax in self._page_axes.items()
                     if ax is None}
        if self.mesh is not None:
            state = jax.device_put(state, self._state_shardings(state),
                                   donate=True)
        return state

    def _donor_tails(self, donor: dict) -> dict:
        return {n: donor[n] for n in self._store.tail_leaves}

    def _tails_template(self) -> dict:
        """Zeroed tails-only donor (size-1 slot axis) for fused activation —
        load_donor restores the tail snapshot into it and, lacking the paged
        leaves, skips the page gather entirely."""
        return {n: jnp.zeros(shape, dt)
                for n, (shape, dt) in self._store.tail_leaves.items()}

    def _build_tables(self, sched, active) -> jnp.ndarray:
        """(B, P) int32 block table for this step: active slots' page lists
        (scratch-padded past coverage), all-scratch rows for idle slots —
        every entry is a valid page id, the fused kernels' index maps fetch
        unconditionally."""
        tabs = np.full((self.batch, self._table_width),
                       self._store.scratch_page, np.int32)
        for slot in active:
            rid = sched.slots[slot].request.rid
            tabs[slot] = self._store.table_row(rid, self._table_width)
        return jnp.asarray(tabs)

    def _first_chunk_embeds(self, req: Request):
        """Per-request media for the FIRST chunk: vlm vision prefix rows /
        audio encoder frames (zeros when the request carries none)."""
        cfg = self.cfg
        if cfg.family == "vlm":
            if req.embeds is not None:
                return jnp.asarray(req.embeds, cfg.dtype)[None]
            return jnp.zeros((1, cfg.vision_prefix, cfg.d_model), cfg.dtype)
        if cfg.family == "audio":
            if req.embeds is not None:
                return jnp.asarray(req.embeds, cfg.dtype)[None]
            return jnp.zeros((1, self.enc_len, cfg.d_model), cfg.dtype)
        return None

    # -- paged serving helpers ------------------------------------------------

    def _share_plan(self, req: Request) -> tuple[str | None, int]:
        """(content key, boundary rows) for the shareable prefix of ``req``,
        or (None, 0) when nothing aligned is shareable. The boundary is the
        largest chunk-aligned token count <= the caller's shared_prefix_len
        hint (default: the whole prompt minus its last token — the first
        token's logits must come from a re-run chunk) whose ROW count (media
        prefix + tokens) is page-aligned: only whole pages are shared."""
        if self._store is None or not self.paged.prefix_sharing:
            return None, 0
        cap = req.prompt_len - 1 if req.shared_prefix_len is None \
            else min(int(req.shared_prefix_len), req.prompt_len - 1)
        chunk = self.policy.chunk
        t = (cap // chunk) * chunk
        if self._store.paged:
            while t >= chunk and (self._prefix + t) % self._store.page:
                t -= chunk
        if t < chunk:
            return None, 0
        toks = np.asarray(req.tokens, np.int64)[:t]
        key = prefix_key(arch=self.cfg.name, page_size=self._store.page,
                         int8=self._store.int8, seed=self._seed,
                         prefix_rows=self._prefix, tokens=toks,
                         embeds=req.embeds)
        return key, self._prefix + t

    def _reserve_paged(self, sched: Scheduler, tasks: list, now, step: int):
        """Paged reservation: admission is a PAGE decision, not a lane one —
        admit up to max_inflight concurrent prefills, attach each to the
        store (prompt pages now, shared prefix retained on a hit), and
        fast-forward the chunk schedule past shared rows. A prompt whose
        prefix is being prefilled by an in-flight publisher DEFERS until the
        entry is published, which is what makes prefill-once exact: the
        followers hit the store instead of racing the publisher."""
        chunk = self.policy.chunk
        while len(tasks) < self._max_inflight:
            req = sched.next_admissible(now())
            if req is None:
                break
            share_key, share_rows = self._share_plan(req)
            if share_key is not None and share_key in self._inflight_keys:
                sched.requeue_front(req)
                break
            bucket = req.bucket or self.policy.assign(req.prompt_len)
            if not bucket:
                bucket = BucketPolicy.round_up(req.prompt_len, chunk)
            req.bucket = bucket
            try:
                shared = self._store.attach(
                    req.rid, prompt_rows=self._prefix + req.prompt_len,
                    share_key=share_key)
            except PagesExhausted:
                # admission saw enough pages, the attach lost the race
                # (tail rounding / concurrent attaches): transient, retry
                sched.requeue_front(req)
                break
            padded = np.zeros((1, bucket), np.int64)
            padded[0, :req.prompt_len] = np.asarray(req.tokens, np.int64)
            task = _PrefillTask(
                req=req, slot=-1, padded=padded, n_chunks=bucket // chunk,
                donor=self._new_donor(),
                share_key=share_key, share_rows=share_rows)
            if shared:
                # prefix hit: seed the donor from the shared pages (+ the
                # boundary tail snapshot) and skip the chunks they cover —
                # the shared prompt rows are never prefilled again
                task.donor = self._store.load_donor(req.rid, task.donor)
                task.fill = shared
                task.chunk_idx = (shared - self._prefix) // chunk
            elif share_key is not None:
                task.publish = True
                self._inflight_keys[share_key] = req.rid
            sched.reserve_unplaced(req, step)
            tasks.append(task)

    def _activate_parked(self, sched: Scheduler, state, pending_host,
                         pos_host, temps_host, histories):
        """Drain parked (resident, lane-less) requests into free lanes,
        FIFO: gather the request's pages back into a fresh donor
        (cache_page_read; int8 pages dequantize here), graft it, and resume
        decoding at its committed fill."""
        for slot in sched.free_slots():
            if not self._parked:
                break
            rid = next(iter(self._parked))
            info = self._parked.pop(rid)
            self._store.pin(rid)        # hot again: rehydrate + no spilling
            if self._fused:
                # tails-only restore: the paged leaves stay in the pools and
                # the next step reads them through the block table — the
                # page->lane gather the lane path would run here is the
                # bytes we count as eliminated
                donor = self._store.load_donor(rid, self._tails_template())
                self._tail_restores += 1
                self._gather_bytes_eliminated += \
                    self._store.requests[rid].fill * self._store.fp_row_bytes
            else:
                donor = self._store.load_donor(rid, self._new_donor())
                self._lane_activations += 1
            validate_donor(state, donor, self.model.state_batch_axes(state))
            state = self._insert(state, donor, slot)
            sched.place_parked(rid, slot)
            temps_host[slot] = info["temp"]
            pending_host[slot] = info["pending"]
            pos_host[slot] = info["fill"]
            histories[slot] = info["history"]
            self._act_seq += 1
            self._act_stamp[slot] = self._act_seq
            if self._spec_policy is not None:
                self._spec_policy.reset(slot)
            if self._drafter is not None:
                self._drafter.on_graft(rid, slot, histories[slot])
        return state

    def _preempt_slot(self, slot: int, sched: Scheduler, state, histories):
        """Page exhaustion: evict the latest-activated decoding request from
        its lane, free its pages, and requeue a CONTINUATION at the queue
        head — prompt = everything the model has consumed, resume_token =
        the emitted-but-unconsumed pending token. Re-prefilling those rows
        reproduces the evicted cache exactly (chunked prefill is
        token-for-token identical to decode), so preemption is lossless."""
        req, m = sched.preempt(slot)
        rid = req.rid
        hist = histories.pop(slot)
        prev = self._resumed.get(rid)
        self._resumed[rid] = {
            # original identity survives any number of preemptions
            "prompt_len": prev["prompt_len"] if prev else m.prompt_len,
            "bucket": prev["bucket"] if prev else m.bucket,
            "admitted_at_step": (prev["admitted_at_step"] if prev
                                 else m.admitted_at_step),
            "gen_len": m.gen_len,
            "ttft_s": m.ttft_s,
            "tokens_out": m.tokens_out,
            "prefill_s": m.prefill_s,
            "decode_s": m.decode_s,
            "preemptions": m.preemptions + 1,
            "spec_proposed": m.spec_proposed,
            "spec_accepted": m.spec_accepted,
            "verify_rounds": m.verify_rounds,
        }
        self._preempt_count += 1
        sched.requeue_front(Request(
            rid=rid, tokens=np.asarray(hist[:-1], np.int64),
            gen_len=m.gen_len, sla_s=req.sla_s, embeds=req.embeds,
            arrival_s=req.arrival_s, temperature=req.temperature,
            shared_prefix_len=req.shared_prefix_len,
            resume_token=int(hist[-1])))
        self._store.free(rid)
        self._act_stamp.pop(slot, None)
        state = self._reset(state, slot)
        if self._drafter is not None:
            self._drafter.on_finish(slot)
        return state

    def _grow_or_preempt(self, active, k_vec, sched, state, pos_host,
                         histories):
        """Before phase 2: every decoding slot's pages must cover the rows
        this step may commit (pos + depth + 1, capped at max_len — verify
        scratch rows beyond max_len only ever hold rejected drafts and are
        never committed). On exhaustion, preempt the LATEST-activated slot
        (LIFO: the one that has sunk the least decode work since
        activation) until the grow fits — possibly the growing slot
        itself."""
        active = list(active)
        for slot in list(active):
            if slot not in active:
                continue
            rid = sched.slots[slot].request.rid
            need = min(int(pos_host[slot]) + int(k_vec[slot]) + 1,
                       self.max_len)
            while True:
                try:
                    self._store.grow(rid, need)
                    break
                except PagesExhausted:
                    victims = [s for s in active if s != slot] or [slot]
                    victim = max(victims,
                                 key=lambda s: self._act_stamp.get(s, -1))
                    state = self._preempt_slot(victim, sched, state,
                                               histories)
                    active.remove(victim)
                    k_vec[victim] = 0
                    if victim == slot:
                        break
        return active, state

    def _complete_paged(self, task: _PrefillTask, sched: Scheduler, state,
                        now, outputs, histories, pending_host, pos_host,
                        temps_host):
        """Prefill completion in paged mode: commit the donor's rows past
        the shared boundary into pages (cache_page_write; int8 quantizes
        here), publish the prefix on a miss, sample the first token (or
        resume a preemption's pending token), then activate into a free
        lane — or PARK: the request stays resident in pages only, counted
        by resident_requests, and activates when a lane frees. Returns
        (state, first-tokens emitted: 0 for a resumed continuation)."""
        req, rid = task.req, task.req.rid
        tail = self._store.snapshot_tail(task.donor) \
            if self._store.tail_leaves else None
        self._store.store_donor(rid, task.donor, fill=task.fill, tail=tail)
        if task.publish:
            self._store.publish_prefix(rid, task.share_key,
                                       n_rows=task.share_rows,
                                       tail=task.boundary_tail)
            self._inflight_keys.pop(task.share_key, None)
        m = sched.unplaced_metrics(rid)
        stash = self._resumed.pop(rid, None)
        if stash is not None:
            for name, val in stash.items():
                setattr(m, name, val)
        m.prefill_s += task.prefill_s
        temp = self._slot_temperature(req)
        gen_inc = 0
        if req.resume_token is not None:
            first = int(req.resume_token)
        else:
            first = int(np.asarray(self._sample(
                jnp.asarray(task.first_logits), self._next_key(),
                jnp.asarray([temp], np.float32)))[0])
            outputs[rid] = [first]
            gen_inc = 1
            sched.first_token_unplaced(rid, now())
        history = [int(t) for t in np.asarray(req.tokens)] + [first]
        if m.tokens_out >= m.gen_len:
            # gen_len == 1: finished without ever taking a lane
            sched.finish_unplaced(rid, now())
            self._store.free(rid)
            return state, gen_inc
        free = sched.free_slots()
        if free:
            slot = free[0]
            donor = self._donor_tails(task.donor) if self._fused \
                else task.donor
            validate_donor(state, donor,
                           self.model.state_batch_axes(state))
            state = self._insert(state, donor, slot)
            sched.place_parked(rid, slot)
            temps_host[slot] = temp
            pending_host[slot] = first
            pos_host[slot] = task.fill
            histories[slot] = history
            self._act_seq += 1
            self._act_stamp[slot] = self._act_seq
            if self._spec_policy is not None:
                self._spec_policy.reset(slot)
            if self._drafter is not None:
                self._drafter.on_graft(rid, slot, history)
        else:
            self._parked[rid] = {"pending": first, "fill": task.fill,
                                 "temp": temp, "history": history}
            self._store.unpin(rid)      # parked: cold, host-spillable
        return state, gen_inc

    def jit_cache_sizes(self) -> dict:
        """Compiled-entry counts of the engine's jitted device functions —
        the probe behind the "never runs a shape it hasn't compiled" claim
        (bounded by len(buckets) + 1 per family). ``_cache_size`` is a
        private jax API: report -1 rather than dying if it moves."""

        def sz(fn) -> int:
            try:
                return int(fn._cache_size())
            except Exception:
                return -1

        sizes = {"prefill_chunk": sz(self._chunk), "decode": sz(self._decode)}
        if self._verify is not None:
            sizes["verify"] = sz(self._verify)
        if self._commit is not None:
            sizes["commit"] = sz(self._commit)
        return sizes

    # -- async ingestion ------------------------------------------------------

    def submit(self, req: Request) -> None:
        """Hand a request to a RUNNING engine loop: it is drained into the
        scheduler at the next step boundary and stamped/gated by its
        arrival_s like any trace-driven request."""
        self._inbox.append(req)

    # -- the serving loop -----------------------------------------------------

    def run(self, requests: list[Request]) -> dict:
        if self.mesh is None:
            return self._run(requests)
        # the steps trace under the mesh, so the library's Pallas kernels
        # run per shard (the TPU compiler cannot partition them itself)
        with jax.set_mesh(self.mesh):
            return self._run(requests)

    def _run(self, requests: list[Request]) -> dict:
        rids = [r.rid for r in requests]
        if len(set(rids)) != len(rids):
            raise ValueError("duplicate request rids (outputs and metrics "
                             "are keyed by rid)")
        bad = [r.rid for r in requests if r.gen_len < 1]
        if bad:
            raise ValueError(f"gen_len must be >= 1 (requests {bad}); the "
                             "first token always comes from prefill")
        sched = Scheduler(self.batch, admission=self.cost_model)
        # paged run-state (parking, preemption stashes, publisher locks)
        self._parked, self._resumed, self._inflight_keys = {}, {}, {}
        self._act_stamp, self._act_seq, self._preempt_count = {}, 0, 0
        t0 = time.perf_counter()
        now = lambda: time.perf_counter() - t0  # noqa: E731
        for r in requests:
            sched.submit(r, now())

        state = self._init_state()
        # host mirrors of per-slot decode-loop state: the pending token
        # (emitted but not yet consumed by the model), the cache fill, the
        # sampling temperature, and — for the drafter — the committed token
        # history (prompt + emitted)
        pending_host = np.zeros(self.batch, np.int64)
        pos_host = np.zeros(self.batch, np.int64)
        temps_host = np.full(self.batch, self.sampling.temperature,
                             np.float32)
        histories: dict[int, list[int]] = {}
        outputs: dict[str, list[int]] = {}
        tasks: list[_PrefillTask] = []
        step_log: list[dict] = []
        step = 0
        padded_steady = 0
        generated = 0
        prefill_tokens_total = 0
        decode_emitted = 0          # tokens emitted by phase-2 steps
        slot_steps = 0              # per-slot phase-2 participations
        decode_steps = 0            # plain (k=0) decode steps
        verify_steps = 0            # speculative verify rounds
        spec_proposed_total = 0
        spec_accepted_total = 0
        spec_slot_rounds = 0        # (slot, round) pairs that speculated
        chunk = self.policy.chunk

        seen_rids = set(rids)
        while sched.has_work() or tasks or self._inbox:
            t_step0 = time.perf_counter()
            # pop-drain (never iterate-then-clear): a concurrent submit()
            # landing between the two would be silently dropped
            while self._inbox:
                r = self._inbox.pop(0)
                # streamed requests get the same validation as run()'s list,
                # but as recorded refusals — one bad submit must not kill
                # the loop for everyone already being served
                if r.rid in seen_rids:
                    sched.refused.append(Refusal(
                        r.rid, "invalid: duplicate rid in this run"))
                    continue
                if r.gen_len < 1:
                    sched.refused.append(Refusal(
                        r.rid, "invalid: gen_len must be >= 1 (the first "
                               "token always comes from prefill)"))
                    continue
                seen_rids.add(r.rid)
                sched.submit(r, now())
            sched.release(now())

            # -- reservation: every free slot starts a chunk schedule --------
            # (paged mode: activation + admission are PAGE decisions — drain
            # parked requests into freed lanes, then admit lane-less)
            if self._store is not None:
                state = self._activate_parked(sched, state, pending_host,
                                              pos_host, temps_host,
                                              histories)
                self._reserve_paged(sched, tasks, now, step)
            else:
                while True:
                    free = sched.free_slots()
                    if not free:
                        break
                    req = sched.next_admissible(now())
                    if req is None:
                        break
                    bucket = req.bucket or self.policy.assign(req.prompt_len)
                    if not bucket:
                        # admission off + prompt beyond the largest bucket:
                        # still cover the whole prompt in whole chunks (the
                        # max_len overrun guard stays the only hard stop)
                        bucket = BucketPolicy.round_up(req.prompt_len, chunk)
                    req.bucket = bucket
                    padded = np.zeros((1, bucket), np.int64)
                    padded[0, :req.prompt_len] = np.asarray(req.tokens,
                                                            np.int64)
                    tasks.append(_PrefillTask(
                        req=req, slot=free[0], padded=padded,
                        n_chunks=bucket // chunk,
                        donor=self._new_donor()))
                    sched.reserve(free[0], req, step)

            # -- unified step, phase 1: one chunk per in-flight prefill ------
            ran: list[_PrefillTask] = []
            for task in tasks:
                c0 = task.chunk_idx * chunk
                seg = jnp.asarray(task.padded[:, c0:c0 + chunk], jnp.int32)
                n_real = max(0, min(task.req.prompt_len - c0, chunk))
                embeds = self._first_chunk_embeds(task.req) \
                    if task.chunk_idx == 0 else None
                writes = chunk + (self._prefix if task.chunk_idx == 0 else 0)
                if task.fill + writes > self.max_len:
                    raise RuntimeError(
                        f"prefill chunk for {task.req.rid!r} would overrun "
                        f"max_len={self.max_len} (admission off?)")
                last, task.donor = self._chunk(
                    self.params, task.donor, seg,
                    jnp.int32(task.fill), jnp.int32(n_real), embeds)
                if self._drafter is not None:
                    # a draft-model drafter mirrors the chunk schedule into
                    # its own donor (no-op for the n-gram drafter)
                    self._drafter.on_chunk(task.req.rid,
                                           task.padded[:, c0:c0 + chunk],
                                           n_real)
                task.chunk_idx += 1
                ran.append(task)
                if task.chunk_idx == 1:
                    task.fill += self._prefix       # vlm vision rows
                if n_real:
                    task.fill += n_real
                    task.first_logits = np.asarray(last)    # syncs the chunk
                if (task.publish and task.boundary_tail is None
                        and self._store is not None
                        and self._store.tail_leaves
                        and task.fill >= task.share_rows):
                    # recurrent-tail families: the prefix entry must restore
                    # the state AT the boundary, so snapshot it the moment
                    # the fill crosses (boundary is chunk-aligned — the
                    # crossing is exact)
                    task.boundary_tail = self._store.snapshot_tail(task.donor)
            chunk_tokens = len(ran) * chunk
            prefill_tokens_total += chunk_tokens

            active = sched.active_slots()
            if sched.queue and self._store is None:
                # released queue still has work: every free, unreserved slot
                # this step is waste. With per-step admission this is 0 by
                # construction — the counter is a tripwire so any future
                # scheduling policy that delays admission surfaces its cost
                # here instead of silently regressing
                padded_steady += self.batch - len(active) - len(tasks)

            # -- phase 2: one decode OR verify step over every occupied slot -
            emitted_this_step = 0
            # per-slot speculation depth, priced per step: clipped to the
            # slot's remaining generation budget, 0 when the cost channel
            # says drafting doesn't pay (or speculation is off)
            k_vec = np.zeros(self.batch, np.int64)
            if active and self._spec_policy is not None:
                for slot in active:
                    s_ = sched.slots[slot]
                    remaining = s_.request.gen_len - s_.metrics.tokens_out
                    k_vec[slot] = self._spec_policy.depth(
                        slot, int(pos_host[slot]), remaining)
            if active and self._store is not None:
                # page growth for the rows this step commits; exhaustion
                # preempts LIFO back to the queue head
                active, state = self._grow_or_preempt(
                    active, k_vec, sched, state, pos_host, histories)
            if active:
                if int(pos_host[active].max()) >= self.max_len:
                    # reachable only with admission=False (admission's
                    # over_budget check forbids it): fail loudly rather than
                    # silently clobbering the last cache row
                    raise RuntimeError(
                        f"active slot position {int(pos_host[active].max())} "
                        f"overran max_len={self.max_len}")
                K = int(k_vec.max())
                pos_vec = jnp.asarray(pos_host, jnp.int32)
                temps = jnp.asarray(temps_host)
                tables = pools = None
                if self._fused:
                    # steady-state fused path: this step reads/writes the
                    # pools THROUGH the block table — no page->lane gather
                    tables = self._build_tables(sched, active)
                    pools = self._sharded_device_pools()
                if K == 0:
                    # degraded path: EXACTLY today's decode step — same jitted
                    # fn, same sampler call, same key draw — so k=0
                    # speculation is token-for-token identical to PR 5 decode
                    tokens = jnp.asarray(pending_host[:, None], jnp.int32)
                    if self._fused:
                        logits, state, pools = self._decode_paged(
                            self.params, state, pools, tables, tokens,
                            pos_vec)
                        self._store.set_device_pools(pools)
                    else:
                        logits, state = self._decode(self.params, state,
                                                     tokens, pos_vec)
                    toks = np.asarray(self._sample(logits, self._next_key(),
                                                   temps))
                    decode_steps += 1
                    for slot in active:
                        rid = sched.slots[slot].request.rid
                        sched.step_done(slot)
                        pos_host[slot] += 1
                        pending_host[slot] = int(toks[slot])
                        outputs[rid].append(int(toks[slot]))
                        if slot in histories:
                            histories[slot].append(int(toks[slot]))
                        generated += 1
                        emitted_this_step += 1
                else:
                    # speculative round: draft -> ONE ragged batched verify ->
                    # accept longest prefix + corrected token -> commit
                    drafts = self._drafter.propose(active, histories, k_vec,
                                                   self.batch, K)
                    span_np = np.concatenate(
                        [pending_host[:, None], drafts], axis=1)
                    # the whole table takes the slab write (inactive rows
                    # included), so the guard covers every slot's position
                    assert_span_fits(pos_host, K + 1, self._state_len)
                    span = jnp.asarray(span_np, jnp.int32)
                    if self._fused:
                        logits, state, pools = self._verify_paged(
                            self.params, state, pools, tables, span, pos_vec)
                    else:
                        logits, state = self._verify(self.params, state, span,
                                                     pos_vec)
                    # sample the target token at EVERY span row (per-slot
                    # temperature); row j validates draft j+1, row m yields
                    # the corrected token for a slot accepting m drafts
                    tgt = np.asarray(self._sample(logits, self._next_key(),
                                                  temps))
                    m_vec = accept_span(drafts, tgt, k_vec)
                    n_commit = np.zeros(self.batch, np.int64)
                    for slot in active:
                        n_commit[slot] = m_vec[slot] + 1
                    if self._fused:
                        if self._commit_paged is not None:
                            state, pools = self._commit_paged(
                                self.params, state, pools, tables, span,
                                pos_vec, jnp.asarray(n_commit, jnp.int32))
                        self._store.set_device_pools(pools)
                    elif self._commit is not None:
                        # recurrent/hybrid: replay the accepted prefix of the
                        # span through the chunked-prefill path (per-slot
                        # n_commit real rows; 0 == exact identity, so
                        # rejected or inactive slots are never perturbed)
                        state = self._commit(
                            self.params, state, span, pos_vec,
                            jnp.asarray(n_commit, jnp.int32))
                    verify_steps += 1
                    for slot in active:
                        rid = sched.slots[slot].request.rid
                        m = int(m_vec[slot])
                        emit = [int(t) for t in drafts[slot, :m]]
                        emit.append(int(tgt[slot, m]))
                        sched.step_done(slot, n=len(emit))
                        if k_vec[slot] > 0:
                            sched.spec_round(slot, proposed=int(k_vec[slot]),
                                             accepted=m)
                            self._spec_policy.update(slot, int(k_vec[slot]),
                                                     m)
                            spec_proposed_total += int(k_vec[slot])
                            spec_accepted_total += m
                            spec_slot_rounds += 1
                        pos_host[slot] += len(emit)
                        pending_host[slot] = emit[-1]
                        outputs[rid].extend(emit)
                        if slot in histories:
                            histories[slot].extend(emit)
                        if self._drafter is not None:
                            self._drafter.on_commit(slot, m)
                        generated += len(emit)
                        emitted_this_step += len(emit)

            if active and self.mesh is not None:
                # steady-state audit: the step must have returned state (and
                # pools) in exactly the rule shardings it received them with
                self._check_steady_sharding(
                    state, self._store.device_pools() if self._fused else None)

            # -- phase 3: shared-step time attribution (prefill vs decode) ---
            decode_emitted += emitted_this_step
            slot_steps += len(active)
            t_step = time.perf_counter() - t_step0
            pre_share, _ = sched.attribute_step_time(
                t_step, chunk_tokens, active,
                decode_tokens=emitted_this_step)
            for task in ran:
                task.prefill_s += pre_share / max(len(ran), 1)

            if ran or active:
                step_log.append({"step": step,
                                 "prefill_rids": [t.req.rid for t in ran],
                                 "chunks": len(ran),
                                 "decoded": len(active),
                                 "emitted": emitted_this_step})

            # -- phase 4: completions (finished prefills + finished decodes) -
            for task in list(tasks):
                if task.chunk_idx < task.n_chunks:
                    continue
                if self._store is not None:
                    state, gen_inc = self._complete_paged(
                        task, sched, state, now, outputs, histories,
                        pending_host, pos_host, temps_host)
                    generated += gen_inc
                    tasks.remove(task)
                    continue
                # prefill complete: graft the donor into its reserved slot,
                # sample the first token, occupy
                slot = task.slot
                temps_host[slot] = self._slot_temperature(task.req)
                first = int(np.asarray(self._sample(
                    jnp.asarray(task.first_logits), self._next_key(),
                    jnp.asarray(temps_host[slot:slot + 1])))[0])
                validate_donor(state, task.donor,
                               self.model.state_batch_axes(state))
                state = self._insert(state, task.donor, slot)
                sched.place(task.req, slot)
                sched.add_prefill_time(slot, task.prefill_s)
                sched.first_token(slot, now())
                generated += 1
                outputs[task.req.rid] = [first]
                pending_host[slot] = first
                pos_host[slot] = task.fill
                # committed token history (prompt + emitted): the drafter's
                # lookup corpus, reset on every slot reuse
                histories[slot] = [int(t) for t in
                                   np.asarray(task.req.tokens)] + [first]
                if self._spec_policy is not None:
                    self._spec_policy.reset(slot)
                if self._drafter is not None:
                    self._drafter.on_graft(task.req.rid, slot,
                                           histories[slot])
                tasks.remove(task)
                if sched.slot_done(slot):           # gen_len == 1 edge case
                    sched.finish(slot, now())
                    state = self._reset(state, slot)
                    if self._drafter is not None:
                        self._drafter.on_finish(slot)
            for slot in list(active):
                if sched.slot_done(slot):
                    rid_done = sched.slots[slot].request.rid
                    sched.finish(slot, now())
                    state = self._reset(state, slot)
                    if self._store is not None:
                        self._store.free(rid_done)
                        self._act_stamp.pop(slot, None)
                    if self._drafter is not None:
                        self._drafter.on_finish(slot)

            if ran or active:
                step += 1           # a unified step actually did device work
            elif not sched.active_slots() and not tasks:
                nxt = sched.next_arrival_s()
                if nxt is not None and not sched.queue and not self._inbox:
                    # idle until the next scheduled arrival
                    time.sleep(max(0.0, min(nxt - now(), 0.05)))

        wall = max(now(), 1e-9)
        finished = sched.finished
        ttfts = [m.ttft_s for m in finished]

        def pct(xs, q):
            return float(np.percentile(xs, q)) if xs else 0.0

        by_bucket: dict[int, list[float]] = {}
        for m in finished:
            by_bucket.setdefault(m.bucket, []).append(m.ttft_s)
        ttft_by_bucket = {
            b: {"n": len(xs), "p50_s": pct(xs, 50), "p90_s": pct(xs, 90),
                "p99_s": pct(xs, 99)}
            for b, xs in sorted(by_bucket.items())
        }

        report = {
            "arch": self.cfg.name,
            "requests": len(finished),
            "generated_tokens": generated,
            "decode_tokens_per_s": generated / wall,
            "steps": step,
            "wall_s": wall,
            "padded_slot_steps_steady": padded_steady,
            "prefill_chunk": chunk,
            "buckets": list(self.policy.buckets),
            "prefill_tokens": prefill_tokens_total,
            "ttft_s_mean": float(np.mean(ttfts)) if ttfts else 0.0,
            "ttft_by_bucket": ttft_by_bucket,
            "sla_hit_rate": sched.sla_hit_rate(),
            "slot_reuse": sched.slot_reuse(),
            "admission_log": sched.admission_log,
            "step_log": step_log,
            "jit_cache": self.jit_cache_sizes(),
            "per_request": [asdict(m) for m in finished],
            "refused": [{"rid": r.rid, "reason": r.reason}
                        for r in sched.refused],
            "outputs": outputs,
        }
        if self.mesh is not None:
            shards = dist_sharding.mesh_shards(self.mesh)
            param_bytes = sum(x.nbytes for x in jax.tree.leaves(self.params))
            state_bytes = sum(x.nbytes for x in jax.tree.leaves(state))
            pool_bytes = 0
            if self._store is not None:
                pool_bytes = self._store.hbm_bytes_resident()
            report["mesh"] = {
                "axes": dist_sharding.mesh_axis_sizes(self.mesh),
                "shards": shards,
                "dp": dist_sharding.dp_size(self.mesh),
                "tp": dist_sharding.tp_size(self.mesh),
                # the compiled-once / zero-resharding claim, audited per step
                "reshard_events": self._reshard_events,
                "param_bytes_per_shard": param_bytes / shards,
                "state_bytes_per_shard": state_bytes / shards,
                "hbm_resident_bytes_per_shard":
                    (param_bytes + state_bytes + pool_bytes) / shards,
                "comms_bytes_per_step":
                    self.cost_model.comms_bytes_per_step()
                    if self.cost_model is not None else 0.0,
            }
            if self.cost_model is not None:
                info = self.cost_model.mesh_info()
                if info is not None:
                    report["mesh"]["pricing"] = info
        if self.cost_model is not None:
            report["cost_model"] = {
                "decode_bytes_per_step": self.cost_model.decode_bytes_per_step(),
                "step_seconds": self.cost_model.step_seconds(),
                "prefill_seconds_largest_bucket":
                    self.cost_model.prefill_seconds(self.policy.buckets[-1]),
            }
            if self.spec is not None:
                report["cost_model"]["verify_seconds_k_max"] = \
                    self.cost_model.verify_seconds(self._k_max)
        if self._store is not None:
            st = self._store
            budget_bytes = st.n_pages * st.page_bytes
            contig_slot = max(st.contiguous_bytes_per_slot(self.max_len), 1)
            report["paged"] = {
                "page_size": st.page,
                "page_bytes": st.page_bytes,
                "n_pages": st.n_pages,
                "hbm_budget_bytes": budget_bytes,
                # bytes priced from ACTUAL pages allocated, not worst case
                "hbm_bytes_resident": st.hbm_bytes_resident(),
                "hbm_bytes_resident_peak": st.pages_used_peak * st.page_bytes,
                "pages_used_peak": st.pages_used_peak,
                "resident_requests": st.resident_requests(),
                "resident_requests_peak": st.resident_peak,
                # what a contiguous max-len slot table could hold at the
                # SAME HBM budget — the residency headline's denominator
                "contiguous_resident_bound": budget_bytes // contig_slot,
                "prefix_hits": st.prefix_store.hits,
                "prefix_misses": st.prefix_store.misses,
                "prefix_entries": len(st.prefix_store.entries),
                "cow_copies": st.cow_copies,
                "preemptions": self._preempt_count,
                "int8": st.int8,
                "fused": self._fused,
                # host spill tier: cold unshared pages evicted to host RAM
                "spills": st.spills,
                "rehydrates": st.rehydrates,
                "spilled_pages": st.spilled_pages(),
                "host_spill_bytes": st.host_spill_bytes(),
                # fused-decode accounting: lane_activations counts full
                # page->lane gathers (fallback families only); fused
                # activations restore just the recurrent tail and skip the
                # KV gather entirely — gather_bytes_eliminated is the fp
                # bytes those skipped gathers would have moved
                "lane_activations": self._lane_activations,
                "tail_restores": self._tail_restores,
                "gather_bytes_eliminated": self._gather_bytes_eliminated,
                "gather_bytes_eliminated_per_step":
                    self._gather_bytes_eliminated
                    / max(decode_steps + verify_steps, 1),
            }
        if self.spec is not None:
            # accepted-token rate + mean accepted span, overall and by bucket
            by_b: dict[int, list[int]] = {}
            for m in finished:
                acc = by_b.setdefault(m.bucket, [0, 0, 0, 0])
                acc[0] += 1
                acc[1] += m.spec_proposed
                acc[2] += m.spec_accepted
                acc[3] += m.verify_rounds
            accept_by_bucket = {
                b: {"n": n, "proposed": p, "accepted": a,
                    "accepted_rate": a / max(p, 1),
                    # each speculating round emits accepted + 1 corrected
                    "mean_accepted_span": (a + r) / max(r, 1)}
                for b, (n, p, a, r) in sorted(by_b.items())
            }
            report["spec"] = {
                "drafter": self.spec.drafter,
                "k_max": self._k_max,
                "decode_steps": decode_steps,
                "verify_steps": verify_steps,
                "drafted_tokens": spec_proposed_total,
                "accepted_tokens": spec_accepted_total,
                "accepted_rate":
                    spec_accepted_total / max(spec_proposed_total, 1),
                "mean_accepted_span":
                    (spec_accepted_total + spec_slot_rounds)
                    / max(spec_slot_rounds, 1),
                # the speedup headline: < 1.0 means speculation emitted more
                # tokens than it ran phase-2 device steps
                "steps_per_emitted_token":
                    (decode_steps + verify_steps) / max(decode_emitted, 1),
                # batching-independent version: per-SLOT step participations
                # per emitted token — exactly 1.0 for plain decode, < 1.0
                # iff verify rounds accepted drafts
                "slot_steps_per_emitted_token":
                    slot_steps / max(decode_emitted, 1),
                "accept_by_bucket": accept_by_bucket,
            }
        return report
