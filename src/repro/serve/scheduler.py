"""Slot-table scheduler for per-step continuous batching.

Pure host-side control plane — no jax in here. The engine owns the device
state; the scheduler owns the request stream (arrival-gated queue), the
per-slot lifecycle (free -> reserved-for-prefill -> occupied -> free),
per-request SLA/deadline accounting, and the admission decision. Admission is
roofline-informed: the cost model consumes the SAME analytic ``lib.cost()``
terms the generator selected the primitive implementations with (PAPER.md
§cost channel), so "can this request meet its deadline on this hardware at
this batch size" is answered from the UPD cost formulas + the v5e roofline
constants, not from guesswork.

Arrivals are asynchronous: ``submit()`` may be called with a future
``arrival_s`` (a trace) or at any wall moment (a live caller); a request
becomes visible to admission only once ``now >= arrival_s``, and every
latency metric is measured from that arrival.

Prompts are length-bucketed before admission (:class:`BucketPolicy`): each
prompt is padded to the smallest UPD-declared bucket size, so the engine only
ever runs prefill shapes from a small declared set — the ARM-SVE
vector-length-agnostic discipline applied to serving. Bucket sizes and the
prefill chunk size are UPD data (``attention_prefill_chunk``'s ``serve:``
block in ``tsl_data/primitives/seq.yaml``), not engine constants.

Refusals are permanent and carry a reason (``over_budget`` — the request's
BUCKET does not fit the slot table's max_len or exceeds the largest declared
bucket; ``sla_infeasible`` — even the best-case estimate misses its
deadline), so callers can re-shape and resubmit rather than letting a doomed
request occupy a slot.
"""

from __future__ import annotations

import heapq
import logging
from collections import deque
from dataclasses import dataclass, field

from repro.launch.roofline import HBM_BW, ICI_BW, PEAK_FLOPS

log = logging.getLogger(__name__)


def _mesh_dp_tp(mesh) -> tuple[int, int]:
    """(data-parallel, tensor-parallel) sizes of a mesh — shape-only
    introspection (``axis_names`` + device-array shape), so it works on
    ``jax.sharding.Mesh`` AND the shape-only fakes tests use, and keeps
    this module jax-free."""
    if mesh is None:
        return 1, 1
    import numpy as np
    sizes = dict(zip(tuple(mesh.axis_names), tuple(np.shape(mesh.devices))))
    tp = max(1, sizes.get("model", 1))
    dp = 1
    for name, size in sizes.items():
        if name != "model":
            dp *= max(1, size)
    return dp, tp

# (primitive, term) pairs already warned about — the analytic fallback fires
# once per step otherwise and would flood the serving logs
_warned_cost_terms: set[tuple[str, str]] = set()


def _cost_fallback_warn(primitive: str, term: str) -> None:
    """A generated package missing a priced cost term is a corpus defect
    (TSL-Check flags it statically as TSL014); warn ONCE per (primitive,
    term) so the silent analytic fallback is attributable in logs. The
    ``comms`` term gets its own wording: it prices MESH collective traffic,
    so a gap there mis-prices sharded serving specifically — distinct from a
    missing ``flops``/``bytes`` term, which mis-prices single-device
    roofline admission."""
    key = (primitive, term)
    if key in _warned_cost_terms:
        return
    _warned_cost_terms.add(key)
    if term == "comms":
        log.warning(
            "TSL014: generated library has no 'comms' cost term on %r — "
            "mesh-sharded admission prices per-step collective bytes from "
            "the analytic ring model instead (run `python -m repro.core "
            "analyze` to lint the UPD cost channel)", primitive)
    else:
        log.warning(
            "TSL014: generated library has no cost term %r/%r — admission "
            "falls back to the analytic formula (run `python -m repro.core "
            "analyze` to lint the UPD cost channel)", primitive, term)

# fallbacks when the UPD corpus is unavailable (mirrors the serve: block on
# the attention_prefill_chunk primitive)
DEFAULT_PREFILL_CHUNK = 8
DEFAULT_BUCKETS = (8, 16, 32, 64, 128, 256, 512)


def upd_serve_defaults() -> dict:
    """The ``serve:`` block declared on the attention_prefill_chunk
    primitive: {"chunk": int, "buckets": [int, ...]}. Falls back to module
    defaults if the corpus (or the block) is missing — the serving path must
    not die because a slimmed UPD dropped one primitive."""
    try:
        from repro.core import load_corpus

        extra = load_corpus().primitives["attention_prefill_chunk"].extra
        blk = dict(extra["serve"])
        return {"chunk": int(blk["chunk"]),
                "buckets": tuple(int(b) for b in blk["buckets"])}
    except Exception:
        return {"chunk": DEFAULT_PREFILL_CHUNK, "buckets": DEFAULT_BUCKETS}


class BucketPolicy:
    """Pad each prompt to the smallest declared bucket size.

    Buckets must be sorted, unique, positive multiples of the prefill chunk
    size — so every padded prompt decomposes into an exact number of
    fixed-shape chunk steps (``bucket // chunk``), and the engine's compiled
    prefill shapes are bounded by the declared set.
    """

    def __init__(self, buckets, chunk: int):
        buckets = tuple(int(b) for b in buckets)
        if not buckets or chunk < 1:
            raise ValueError("need at least one bucket and chunk >= 1")
        if list(buckets) != sorted(set(buckets)):
            raise ValueError(f"buckets must be sorted and unique: {buckets}")
        bad = [b for b in buckets if b <= 0 or b % chunk]
        if bad:
            raise ValueError(
                f"buckets must be positive multiples of chunk={chunk}: {bad}")
        self.buckets = buckets
        self.chunk = int(chunk)

    @classmethod
    def from_upd(cls, chunk: int | None = None,
                 buckets=None) -> "BucketPolicy":
        """Policy from the UPD serve block. A caller-chosen ``chunk`` that
        does not divide the declared buckets rounds each bucket UP to the
        next chunk multiple (deduplicated) — the declared sizes are the
        admissible prompt lengths, the executed schedule stays whole
        chunks."""
        d = upd_serve_defaults()
        chunk = int(chunk if chunk is not None else d["chunk"])
        cand = buckets if buckets is not None else d["buckets"]
        rounded = sorted({cls.round_up(b, chunk) for b in cand})
        return cls(rounded, chunk)

    @staticmethod
    def round_up(n: int, chunk: int) -> int:
        """Smallest multiple of ``chunk`` >= n (the synthetic bucket for
        out-of-policy prompt lengths)."""
        return -(-int(n) // int(chunk)) * int(chunk)

    def assign(self, prompt_len: int) -> int | None:
        """Smallest bucket >= prompt_len, or None if none fits."""
        for b in self.buckets:
            if prompt_len <= b:
                return b
        return None

    def n_chunks(self, bucket: int) -> int:
        return bucket // self.chunk


@dataclass
class Request:
    """One serving request: a prompt, a generation budget, an optional SLA.

    ``sla_s`` is an end-to-end latency deadline in seconds, measured from
    ``arrival_s`` — both admission (projection) and the final hit/miss
    accounting are against it. ``arrival_s`` may be preset to a FUTURE
    engine-clock time (trace-driven arrivals: the request stays invisible to
    admission until then); when left at 0.0 ``submit`` stamps it with the
    submission moment.
    """

    rid: str
    tokens: object                  # prompt token array (1-D, int)
    gen_len: int
    sla_s: float | None = None
    embeds: object | None = None    # per-request media: vlm (prefix, D)
                                    # vision / audio (enc_len, D) frames
    arrival_s: float = 0.0          # preset (trace) or stamped by submit
    bucket: int = 0                 # stamped at admission (BucketPolicy)
    temperature: float | None = None  # per-request override of the engine's
                                      # SamplingConfig (<=0 -> greedy); mixed
                                      # greedy/sampled slots coexist in one
                                      # batched step / verify span
    shared_prefix_len: int | None = None  # paged serving: leading tokens
                                      # shared across requests (a system
                                      # prompt) — the prefix-store boundary
                                      # hint; None lets the engine share the
                                      # whole prompt minus its last token
    resume_token: int | None = None   # paged serving: set on a PREEMPTED
                                      # continuation — the already-emitted
                                      # pending token the engine must resume
                                      # with instead of sampling a first one

    @property
    def prompt_len(self) -> int:
        return int(len(self.tokens))


@dataclass
class RequestMetrics:
    """Per-request accounting the engine reports (and tests assert on)."""

    rid: str
    slot: int = -1
    prompt_len: int = 0
    gen_len: int = 0
    bucket: int = 0                 # padded prompt length (length bucketing)
    tokens_out: int = 0
    ttft_s: float = 0.0             # arrival -> first token (queue + prefill)
    queue_s: float = 0.0            # arrival -> first reservation, plus a
                                    # preempted request's wait to its next
    prefill_s: float = 0.0          # reservation -> first token
    park_s: float = 0.0             # paged: time spent parked, holding its
                                    # tokens with no lane to decode in
    decode_tokens_per_s: float = 0.0   # tokens after the first over the lane
                                       # time (latency - ttft - park_s)
    latency_s: float = 0.0          # arrival -> last token
    sla_s: float | None = None
    sla_met: bool | None = None     # None: no SLA attached
    admitted_at_step: int = -1      # engine step index at slot reservation
    # speculative decoding: tokens_out / decode_tokens_per_s count ONLY
    # target-model-emitted tokens (accepted drafts + the corrected token);
    # rejected drafts are never billed as output
    spec_proposed: int = 0          # draft tokens proposed for this request
    spec_accepted: int = 0          # draft tokens accepted by the target
    verify_rounds: int = 0          # verify steps this request took part in
    preemptions: int = 0            # paged serving: times this request was
                                    # preempted on page exhaustion and
                                    # requeued as a continuation
    # engine-clock time at which each emitted token reached the host (the
    # tokens of one step share their step's stamp)
    token_s: list[float] = field(default_factory=list)


@dataclass
class Refusal:
    rid: str
    reason: str


class CostModelAdmission:
    """Roofline admission driven by the generated library's cost channel.

    A decode step over the full slot table is modeled as memory-bound:
      bytes/step = param bytes (weights stream once per token)
                 + n_attn_layers x lib.cost("attention_decode", "bytes", ...)
      step_s     = bytes / HBM_BW
    Prefill is modeled as compute-bound and priced at the request's BUCKET
    (the padded length actually executed), parameter flops plus the
    ``attention_prefill_chunk`` UPD cost term summed over the chunk schedule.

    Both are deliberately idealized (roofline = best case); a request whose
    deadline fails even the BEST case is hopeless, which makes refusal sound.
    ``lib.cost`` raising KeyError (a generated package without the term) falls
    back to the same formula evaluated analytically — warning once per
    (primitive, term) with finding code TSL014, so the gap is attributable in
    logs and statically catchable (`python -m repro.core analyze`) instead of
    silently mispricing admission.
    """

    def __init__(self, cfg, batch: int, max_len: int,
                 enc_len: int | None = None,
                 policy: BucketPolicy | None = None,
                 mesh=None):
        self.cfg = cfg
        self.batch = batch
        self.max_len = max_len
        self.enc_len = enc_len          # audio: fixed cross K/V length
        self.policy = policy            # None -> exact-length admission
        self.prefix = cfg.decode_prefix
        self.param_bytes = cfg.param_count(
            active_only=(cfg.family == "moe")) * self._dtype_bytes()
        self._attn_layers = self._n_attn_layers()
        self._step_s = None         # computed lazily, cached (pure shapes)
        # mesh-aware pricing: params and slot state are sharded over every
        # device (dist.sharding rules), so HBM traffic divides by the total
        # shard count, while the TP axis adds per-layer collective bytes
        # priced by the UPD ``comms`` term against the interconnect roofline
        self.mesh = mesh
        self.dp, self.tp = _mesh_dp_tp(mesh)
        self.shards = self.dp * self.tp
        # speculative decoding: the engine sets spec_k > 0 when a drafter is
        # attached; admission then prices decode at the BEST-CASE emitted
        # tokens per second across plain decode and a fully-accepted verify
        # span (roofline admission stays best-case, so refusal stays sound)
        self.spec_k = 0

    def _dtype_bytes(self) -> int:
        return 2 if "16" in self.cfg.dtype else 4

    def _n_attn_layers(self) -> int:
        fam = self.cfg.family
        if fam == "ssm":
            return 0
        if fam == "hybrid":
            return self.cfg.n_layers // max(self.cfg.attn_every, 1)
        if fam == "audio":
            return 2 * self.cfg.n_layers    # decoder self + cross attention
        return self.cfg.n_layers

    def decode_bytes_per_step(self, s: int | None = None) -> float:
        """Bytes one full-slot-table decode step moves (UPD cost channel).

        ``s`` is the cache fill to charge attention reads at; defaults to
        the slot table's max_len (steady-state worst case, reported to
        operators). Admission charges each request at ITS OWN maximal fill
        so a short request in a large slot table is not over-billed."""
        cfg = self.cfg
        s_eff = self.max_len if s is None else s

        def per_layer(s_: int) -> float:
            shapes = dict(B=self.batch, H=cfg.n_heads, KH=cfg.n_kv_heads,
                          S=s_, D=cfg.hd)
            try:
                from repro.tsl_api import cost
                raw = cost("attention_decode", "bytes", **shapes)
            except KeyError:
                _cost_fallback_warn("attention_decode", "bytes")
                # same formula as the UPD term, evaluated analytically
                raw = 2.0 * shapes["B"] * (
                    2 * shapes["KH"] * shapes["S"] + 2 * shapes["H"]
                ) * shapes["D"]
            # UPD bytes formulas follow the bf16 production convention
            # (2 B/elem); rescale so this term and param_bytes use the SAME
            # element size when the serving dtype differs (reduced = f32)
            return raw * (self._dtype_bytes() / 2.0)

        attn = 0.0
        if self._attn_layers:
            if cfg.family == "audio":
                # decoder self-attn reads the rolling cache; cross-attn reads
                # the FIXED enc_len-sized K/V, not max_len
                enc = self.enc_len if self.enc_len is not None else s_eff
                attn = cfg.n_layers * (per_layer(s_eff) + per_layer(enc))
            else:
                attn = self._attn_layers * per_layer(s_eff)
        return self.param_bytes + attn

    def _comms_term(self, primitive: str, fallback: float, **shapes) -> float:
        """One layer's collective bytes from the UPD ``comms`` term (TSL014
        analytic-ring fallback when the generated package lacks it).
        ``comms`` formulas follow the same bf16 wire convention as ``bytes``;
        rescale to the serving dtype."""
        shapes = dict(shapes, TP=self.tp)
        try:
            from repro.tsl_api import cost
            raw = cost(primitive, "comms", **shapes)
        except KeyError:
            _cost_fallback_warn(primitive, "comms")
            raw = fallback * (self.tp - 1) / self.tp
        return raw * (self._dtype_bytes() / 2.0)

    def comms_bytes_per_step(self, s: int | None = None) -> float:
        """Collective bytes ONE decode step moves over the TP axis: a ring
        all-reduce of each layer's output activations, priced by the new
        ``comms`` UPD cost term per layer family (attention_decode /
        ssd_scan / wkv6_scan). Zero off-mesh and on a TP=1 mesh — the
        (TP-1)/TP ring factor vanishes."""
        if self.tp <= 1:
            return 0.0
        cfg = self.cfg
        s_eff = self.max_len if s is None else s
        b, h, d = self.batch, cfg.n_heads, cfg.hd
        total = 0.0
        if self._attn_layers:
            attn = self._comms_term(
                "attention_decode", 4.0 * b * h * d,
                B=b, H=h, KH=cfg.n_kv_heads, S=s_eff, D=d)
            factor = cfg.n_layers * 2 if cfg.family == "audio" \
                else self._attn_layers
            total += factor * attn
        if cfg.family == "ssm":
            kk = cfg.rwkv_head_dim
            hh = cfg.d_model // max(kk, 1)
            total += cfg.n_layers * self._comms_term(
                "wkv6_scan", 4.0 * b * hh * kk,
                B=b, T=1, H=hh, K=kk, V=kk)
        elif cfg.family == "hybrid":
            p = cfg.ssm_head_dim
            hh = (cfg.d_inner_mult * cfg.d_model) // max(p, 1)
            scan_layers = cfg.n_layers - self._attn_layers
            total += scan_layers * self._comms_term(
                "ssd_scan", 4.0 * b * hh * p,
                B=b, T=1, H=hh, P=p, N=cfg.ssm_state)
        return total

    def step_seconds(self, s: int | None = None) -> float:
        if s is not None:
            return (self.decode_bytes_per_step(s) / (self.shards * HBM_BW)
                    + self.comms_bytes_per_step(s) / ICI_BW)
        if self._step_s is None:
            self._step_s = (
                self.decode_bytes_per_step() / (self.shards * HBM_BW)
                + self.comms_bytes_per_step() / ICI_BW)
        return self._step_s

    def verify_seconds(self, k: int, s: int | None = None) -> float:
        """Best-case time of ONE ragged verify step at draft depth k (span
        SV = k+1) over the full slot table: memory-bound like decode —
        param bytes stream once regardless of span width, plus the
        ``attention_verify`` UPD bytes term per attention layer. Recurrent
        and hybrid families pay the commit replay (the accepted prefix runs
        through the chunked-prefill path), modeled as a factor of 2. This is
        the price the SpeculationPolicy weighs against expected accepted
        tokens when choosing a per-slot depth."""
        cfg = self.cfg
        sv = int(k) + 1
        s_eff = self.max_len if s is None else s

        def per_layer(s_: int) -> float:
            shapes = dict(B=self.batch, H=cfg.n_heads, KH=cfg.n_kv_heads,
                          SV=sv, S=s_, D=cfg.hd)
            try:
                from repro.tsl_api import cost
                raw = cost("attention_verify", "bytes", **shapes)
            except KeyError:
                _cost_fallback_warn("attention_verify", "bytes")
                raw = 2.0 * shapes["B"] * (
                    2 * shapes["KH"] * shapes["S"] + 2 * shapes["H"] * sv
                ) * shapes["D"]
            return raw * (self._dtype_bytes() / 2.0)

        attn = 0.0
        if self._attn_layers:
            if cfg.family == "audio":
                enc = self.enc_len if self.enc_len is not None else s_eff
                attn = cfg.n_layers * (per_layer(s_eff) + per_layer(enc))
            else:
                attn = self._attn_layers * per_layer(s_eff)
        commit_factor = 2.0 if cfg.family in ("ssm", "hybrid") else 1.0
        comms_s = 0.0
        if self.tp > 1 and self._attn_layers:
            comms = self._comms_term(
                "attention_verify", 4.0 * self.batch * cfg.n_heads * sv * cfg.hd,
                B=self.batch, H=cfg.n_heads, KH=cfg.n_kv_heads,
                SV=sv, S=s_eff, D=cfg.hd)
            factor = cfg.n_layers * 2 if cfg.family == "audio" \
                else self._attn_layers
            comms_s = factor * comms / ICI_BW
        return ((self.param_bytes + attn) / (self.shards * HBM_BW)
                + comms_s) * commit_factor

    def emit_seconds_per_token(self, s: int | None = None) -> float:
        """Best-case seconds per EMITTED token: plain decode, or — when the
        engine runs speculation — a fully-accepted verify span at spec_k
        (k+1 tokens per step), whichever is cheaper."""
        per_tok = self.step_seconds(s)
        if self.spec_k > 0:
            per_tok = min(per_tok,
                          self.verify_seconds(self.spec_k, s)
                          / (self.spec_k + 1))
        return per_tok

    def prefill_seconds(self, padded_len: int) -> float:
        """Best-case prefill time for ``padded_len`` prompt tokens: parameter
        flops + the attention_prefill_chunk cost term summed over the chunk
        schedule (each chunk priced at its own growing cache fill)."""
        cfg = self.cfg
        n = cfg.param_count(active_only=(cfg.family == "moe"))
        flops = 2.0 * n * padded_len
        if self._attn_layers:
            chunk = self.policy.chunk if self.policy else padded_len
            fills = range(chunk, padded_len + 1, chunk) if chunk else ()

            def chunk_flops(fill: int) -> float:
                shapes = dict(B=1, H=cfg.n_heads, KH=cfg.n_kv_heads,
                              C=chunk, S=self.prefix + fill, D=cfg.hd)
                try:
                    from repro.tsl_api import cost
                    return cost("attention_prefill_chunk", "flops", **shapes)
                except KeyError:
                    _cost_fallback_warn("attention_prefill_chunk", "flops")
                    return 4.0 * shapes["H"] * shapes["C"] * shapes["S"] \
                        * shapes["D"]

            flops += self._attn_layers * sum(chunk_flops(f) for f in fills)
        seconds = flops / (self.shards * PEAK_FLOPS)
        if self.tp > 1 and self._attn_layers:
            chunk = self.policy.chunk if self.policy else padded_len
            n_chunks = padded_len // chunk if chunk else 0
            comms = self._comms_term(
                "attention_prefill_chunk",
                4.0 * chunk * cfg.n_heads * cfg.hd,
                B=1, H=cfg.n_heads, KH=cfg.n_kv_heads, C=chunk,
                S=self.prefix + padded_len, D=cfg.hd)
            seconds += self._attn_layers * n_chunks * comms / ICI_BW
        return seconds

    def mesh_info(self) -> dict | None:
        """Mesh pricing summary for the engine report (None off-mesh):
        axis sizes, the per-shard parameter bytes the roofline divides to,
        and the UPD-priced collective bytes per full-table decode step."""
        if self.mesh is None:
            return None
        return {
            "axes": {"data": self.dp, "model": self.tp},
            "shards": self.shards,
            "param_bytes_per_shard": self.param_bytes / self.shards,
            "comms_bytes_per_step": self.comms_bytes_per_step(),
            "step_seconds": self.step_seconds(),
        }

    def admit(self, req: Request, now_s: float) -> tuple[bool, str]:
        if self.policy is not None:
            bucket = self.policy.assign(req.prompt_len)
            if bucket is None:
                return False, (f"over_budget: prompt {req.prompt_len} exceeds "
                               f"largest bucket {self.policy.buckets[-1]}")
        else:
            bucket = req.prompt_len
        if self.prefix + bucket + req.gen_len > self.max_len:
            return False, (f"over_budget: bucket {bucket} (prompt "
                           f"{req.prompt_len}) + gen {req.gen_len}"
                           + (f" + vision prefix {self.prefix}"
                              if self.prefix else "")
                           + f" > max_len {self.max_len}")
        if req.sla_s is not None:
            waited = max(0.0, now_s - req.arrival_s)
            # charge attention reads at THIS request's maximal cache fill,
            # not max_len: a short request in a large slot table must not be
            # refused on traffic it will never generate
            s_req = self.prefix + bucket + req.gen_len
            projected = (waited + self.prefill_seconds(bucket)
                         + req.gen_len * self.emit_seconds_per_token(s_req))
            if projected > req.sla_s:
                return False, (f"sla_infeasible: projected {projected:.3e}s "
                               f"> sla {req.sla_s:.3e}s")
        req.bucket = bucket
        return True, "ok"


class PagedAdmission(CostModelAdmission):
    """Page-count admission for the paged slot store: admit on pages
    available NOW, not on worst-case bucket bytes.

    The contiguous admission implicitly prices every request at a full
    max-bucket cache reservation (a lane IS that reservation). With paged
    memory the honest price is the pages the request's PROMPT needs at
    attach (decode growth is paid step by step, with preemption as the
    backstop), against the pages allocatable right now — the free list, every
    evictable prefix-store page, AND every host-spillable page (cold unpinned
    requests' exclusive pages: the spill tier evicts them to host RAM on
    demand and rehydrates on next touch, so they are reclaimable without
    losing the request). ``budget`` is any object with
    ``pages_for_rows(rows)`` and ``pages_free()`` (the
    :class:`repro.serve.paging.PagedKVStore` interface; tests inject fakes).

    A page shortage is TRANSIENT (decodes finish, pages free), so it defers
    rather than refuses: the ``defer:`` reason prefix makes
    ``Scheduler.next_admissible`` put the request back at the FRONT of the
    queue instead of recording a permanent refusal. Lane-capacity and SLA
    refusals from the base class stay permanent.

    A preempted continuation (``resume_token`` set) skips the SLA and
    gen-budget re-checks — the original admission already priced the full
    request, and refusing a half-served request would lose emitted tokens —
    but still pays the page check for its (longer) re-prefill prompt.
    """

    def __init__(self, cfg, batch: int, max_len: int, *, budget,
                 enc_len: int | None = None,
                 policy: BucketPolicy | None = None,
                 mesh=None):
        super().__init__(cfg, batch, max_len, enc_len=enc_len, policy=policy,
                         mesh=mesh)
        self.budget = budget

    def mesh_info(self) -> dict | None:
        """Page budgets divide by the shard count too: every pool leaf is
        itself sharded over the mesh, so one LOGICAL page costs
         1/shards of its bytes on each device — reported per shard so
        operators see the budget each device actually holds."""
        info = super().mesh_info()
        if info is None:
            return None
        n_pages = getattr(self.budget, "n_pages", None)
        page_bytes = getattr(self.budget, "page_bytes", None)
        if n_pages is not None and page_bytes is not None:
            info["page_budget_bytes_per_shard"] = \
                n_pages * page_bytes / self.shards
        return info

    def admit(self, req: Request, now_s: float) -> tuple[bool, str]:
        if req.resume_token is not None:
            chunk = self.policy.chunk if self.policy else 1
            bucket = (self.policy.assign(req.prompt_len)
                      if self.policy else None) \
                or BucketPolicy.round_up(req.prompt_len, chunk)
            if self.prefix + bucket + 1 > self.max_len:
                return False, (f"over_budget: continuation prompt "
                               f"{req.prompt_len} cannot re-prefill within "
                               f"max_len {self.max_len}")
            req.bucket = bucket
        else:
            ok, reason = super().admit(req, now_s)
            if not ok:
                return ok, reason
        need = self.budget.pages_for_rows(self.prefix + req.prompt_len)
        free = self.budget.pages_free()
        if need > free:
            return False, (f"defer: needs {need} pages for its prompt, "
                           f"{free} allocatable now")
        return True, "ok"


def _admitted(req: Request, slot: int, step: int,
              now_s: float | None) -> RequestMetrics:
    """Metrics of a request reserved at ``now_s`` (None: not stamped)."""
    return RequestMetrics(
        rid=req.rid, slot=slot, prompt_len=req.prompt_len,
        gen_len=req.gen_len, bucket=req.bucket or req.prompt_len,
        sla_s=req.sla_s, admitted_at_step=step,
        queue_s=0.0 if now_s is None else max(now_s - req.arrival_s, 0.0))


def _first_token(req: Request, m: RequestMetrics, now_s: float) -> None:
    """The first token reached the host at ``now_s``: TTFT, and the prefill
    time since the reservation."""
    m.ttft_s = max(now_s - req.arrival_s, 1e-9)
    m.prefill_s = max(m.ttft_s - m.queue_s, 0.0)
    m.tokens_out = 1
    m.token_s = [now_s]


def _finished(req: Request, m: RequestMetrics, now_s: float) -> None:
    """The last token reached the host at ``now_s``: latency, the decode
    rate over the time the request held a lane, and the SLA verdict."""
    m.latency_s = max(now_s - req.arrival_s, 1e-9)
    lane_s = m.latency_s - m.ttft_s - m.park_s
    m.decode_tokens_per_s = max(m.tokens_out - 1, 0) / max(lane_s, 1e-9)
    if m.sla_s is not None:
        m.sla_met = m.latency_s <= m.sla_s


@dataclass
class _Slot:
    request: Request | None = None     # occupied: decoding
    reserved: Request | None = None    # reserved: prefill chunks in flight
    metrics: RequestMetrics | None = None
    served: int = 0                    # lifetime requests this slot carried

    @property
    def free(self) -> bool:
        return self.request is None and self.reserved is None


class Scheduler:
    """Arrival-gated request stream + slot table + SLA accounting.

    Protocol (driven by the engine once per unified step):
      submit(req, now)                 — enqueue (future arrival_s -> pending)
      release(now)                     — move arrived requests into the queue
      next_admissible(now)             — pop the next request that passes
                                         admission; refused requests are
                                         recorded and dropped
      reserve(slot, req, step)         — slot enters prefill (chunks running)
      place(req, slot)                 — prefill done: slot occupied
      first_token(slot, now)           — TTFT stamp (queue + prefill)
      step_done(slot, n, now)          — n real tokens decoded in this slot,
                                         stamped with the step's time
      finish(slot, now) -> metrics     — request complete, slot freed

    Paged mode adds a lane-less track: reserve_unplaced / first_token_unplaced
    / park / place_parked / finish_unplaced (admission and parking are
    page-count decisions, not lane decisions) and preempt / requeue_front
    (page exhaustion sends a decoding request back to the queue head as a
    resumable continuation).
    """

    def __init__(self, n_slots: int, admission: CostModelAdmission | None = None):
        self.slots = [_Slot() for _ in range(n_slots)]
        self.queue: deque[Request] = deque()
        self.pending: list[tuple[float, int, Request]] = []   # arrival heap
        self._seq = 0
        self.admission = admission
        self.finished: list[RequestMetrics] = []
        self.refused: list[Refusal] = []
        self.admission_log: list[dict] = []   # {step, slot, rid} per admission
        # paged serving: requests admitted WITHOUT a lane (prefilling into a
        # donor, or parked resident in pages awaiting a free lane)
        self.unplaced: dict[str, tuple[Request, RequestMetrics]] = {}
        self._parked_at: dict[str, float] = {}    # rid -> when it parked

    # -- request stream -------------------------------------------------------

    def submit(self, req: Request, now_s: float) -> None:
        """Async-safe ingestion: a request with a future ``arrival_s`` is
        held pending (invisible to admission) until the engine clock reaches
        it; a preset PAST arrival is honored (the wait since then counts
        toward TTFT/SLA); only an unset arrival (0.0) is stamped with the
        submission moment."""
        if req.arrival_s > now_s:
            heapq.heappush(self.pending, (req.arrival_s, self._seq, req))
            self._seq += 1
        else:
            if req.arrival_s <= 0.0:
                req.arrival_s = now_s
            self.queue.append(req)

    def release(self, now_s: float) -> int:
        """Move every pending request whose arrival time has come into the
        admission queue (arrival order). Returns how many arrived."""
        n = 0
        while self.pending and self.pending[0][0] <= now_s:
            _, _, req = heapq.heappop(self.pending)
            self.queue.append(req)
            n += 1
        return n

    def next_arrival_s(self) -> float | None:
        return self.pending[0][0] if self.pending else None

    def next_admissible(self, now_s: float) -> Request | None:
        while self.queue:
            req = self.queue.popleft()
            if self.admission is None:
                return req
            ok, reason = self.admission.admit(req, now_s)
            if ok:
                return req
            if reason.startswith("defer"):
                # transient shortage (paged admission: pages free up as
                # decodes finish): keep FIFO order, try again next step
                self.queue.appendleft(req)
                return None
            self.refused.append(Refusal(req.rid, reason))
        return None

    def requeue_front(self, req: Request) -> None:
        """Put a request at the HEAD of the queue: a preempted continuation
        (it was already being served — it must not wait behind arrivals) or
        an attach that lost a page race."""
        self.queue.appendleft(req)

    # -- slot lifecycle -------------------------------------------------------

    def free_slots(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if s.free]

    def active_slots(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if s.request is not None]

    def reserved_slots(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if s.reserved is not None]

    def reserve(self, slot: int, req: Request, step: int,
                now_s: float | None = None) -> None:
        """Slot enters prefill; ``now_s`` stamps the request's queue wait."""
        s = self.slots[slot]
        if not s.free:
            raise ValueError(f"slot {slot} is not free")
        s.reserved = req
        self.admission_log.append({"step": step, "slot": slot, "rid": req.rid})
        s.metrics = _admitted(req, slot, step, now_s)

    def place(self, req: Request, slot: int, step: int | None = None) -> None:
        s = self.slots[slot]
        if s.reserved is None and s.request is None:
            # direct placement (no reserve phase: unit tests / legacy path)
            self.reserve(slot, req, -1 if step is None else step)
        elif s.reserved is not req and s.reserved is not None:
            raise ValueError(
                f"slot {slot} is reserved by {s.reserved.rid!r}")
        elif s.request is not None:
            raise ValueError(
                f"slot {slot} is occupied by {s.request.rid!r}")
        s.request = req
        s.reserved = None
        s.served += 1

    # -- paged serving: lane-less admission, parking, preemption --------------

    def reserve_unplaced(self, req: Request, step: int,
                         now_s: float | None = None) -> RequestMetrics:
        """Admit a request WITHOUT reserving a lane (paged mode: the prefill
        runs in a donor, and a completed request may stay parked in pages).
        Logged with slot = -1; ``place_parked`` moves it into a lane later,
        carrying these metrics with it."""
        if req.rid in self.unplaced:
            raise ValueError(f"request {req.rid!r} already unplaced")
        self.admission_log.append({"step": step, "slot": -1, "rid": req.rid})
        m = _admitted(req, -1, step, now_s)
        self.unplaced[req.rid] = (req, m)
        return m

    def unplaced_metrics(self, rid: str) -> RequestMetrics:
        return self.unplaced[rid][1]

    def park(self, rid: str, now_s: float) -> None:
        """An unplaced request finished its prefill with no free lane: it
        waits, parked, from ``now_s`` until ``place_parked``."""
        self._parked_at[rid] = now_s

    def place_parked(self, rid: str, slot: int, now_s: float) -> Request:
        """Activate an unplaced request into a free lane at ``now_s``,
        carrying its metrics (TTFT was stamped at prefill completion); the
        time since it parked, if it did, adds to its ``park_s``."""
        s = self.slots[slot]
        if not s.free:
            raise ValueError(f"slot {slot} is not free")
        req, m = self.unplaced.pop(rid)
        if rid in self._parked_at:
            m.park_s += now_s - self._parked_at.pop(rid)
        m.slot = slot
        s.request, s.metrics = req, m
        s.served += 1
        return req

    def first_token_unplaced(self, rid: str, now_s: float) -> None:
        """TTFT stamp for a request finishing prefill without a lane: the
        first token exists (sampled from the last prefill chunk) whether or
        not a lane is free to decode the second one."""
        _first_token(*self.unplaced[rid], now_s)

    def finish_unplaced(self, rid: str, now_s: float) -> RequestMetrics:
        """Complete a request that never got (or no longer needs) a lane —
        the parked gen_len == 1 edge case."""
        req, m = self.unplaced.pop(rid)
        _finished(req, m, now_s)
        self.finished.append(m)
        return m

    def preempt(self, slot: int) -> tuple[Request, RequestMetrics]:
        """Evict a DECODING request from its lane on page exhaustion. The
        request is NOT finished: the engine stashes the metrics, requeues a
        continuation (prompt + emitted tokens, ``resume_token`` set) at the
        queue head, and merges the accounting when the continuation
        completes its re-prefill."""
        s = self.slots[slot]
        if s.request is None:
            raise ValueError(f"slot {slot} is not decoding")
        req, m = s.request, s.metrics
        s.request, s.reserved, s.metrics = None, None, None
        return req, m

    def first_token(self, slot: int, now_s: float) -> None:
        s = self.slots[slot]
        _first_token(s.request, s.metrics, now_s)

    def step_done(self, slot: int, n: int = 1,
                  now_s: float | None = None) -> None:
        """``n`` target-model-emitted tokens landed in this slot this step
        (n > 1: a verify round accepted n-1 drafts + the corrected token;
        rejected drafts are never counted), at ``now_s`` on the host."""
        m = self.slots[slot].metrics
        m.tokens_out += n
        if now_s is not None:
            m.token_s.extend([now_s] * n)

    def spec_round(self, slot: int, proposed: int, accepted: int) -> None:
        """Account one verify round for this slot's request."""
        m = self.slots[slot].metrics
        m.spec_proposed += proposed
        m.spec_accepted += accepted
        m.verify_rounds += 1

    def slot_done(self, slot: int) -> bool:
        s = self.slots[slot]
        return (s.request is not None
                and s.metrics.tokens_out >= s.request.gen_len)

    def finish(self, slot: int, now_s: float) -> RequestMetrics:
        s = self.slots[slot]
        m, req = s.metrics, s.request
        _finished(req, m, now_s)
        s.request, s.reserved, s.metrics = None, None, None
        self.finished.append(m)
        return m

    # -- aggregate view -------------------------------------------------------

    def has_work(self) -> bool:
        return (bool(self.queue) or bool(self.pending)
                or bool(self.active_slots()) or bool(self.reserved_slots())
                or bool(self.unplaced))

    def sla_hit_rate(self) -> float | None:
        scored = [m for m in self.finished if m.sla_met is not None]
        if not scored:
            return None
        return sum(m.sla_met for m in scored) / len(scored)

    def slot_reuse(self) -> list[int]:
        return [s.served for s in self.slots]
