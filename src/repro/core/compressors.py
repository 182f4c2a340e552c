"""Gradient-compressor framework + the paper's non-low-rank baselines.

A compressor replaces the data-parallel gradient all-reduce. The API is
functional (pytree state threaded through the step) so everything jits and
shard_maps:

    comp  = make_compressor(cfg, abstract_grads, stacked=...)
    state = comp.init_state(key)                       # E, warm Q, counters
    g_bar, state, rec = comp.sync(grads, state, comm)  # comm: AxisComm

``sync`` runs *inside* the manual (data, pod) axes of ``jax.shard_map`` —
or under ``jax.vmap(axis_name=...)`` in tests — and returns the synchronized
(averaged, possibly lossy-reconstructed) gradients every worker applies.

Per-leaf routing: every leaf carries a :class:`LeafPolicy` — which method
ships it and with what knobs (rank, bits, topk ratio). The dedicated
compressor classes apply ONE uniform policy (the paper's global config);
:class:`~repro.core.composite.CompositeCompressor` mixes policies per
tensor. Small/1-D tensors (biases, norms, scalars) take the raw ``pmean``
path exactly as in PowerSGD's reference implementation ("rank-1 tensors are
aggregated uncompressed").

The method-specific math lives in :class:`LeafGroupHandler` subclasses that
sync an arbitrary *subset* of the gradient leaves. A dedicated compressor
drives one handler over every leaf; the composite drives one handler per
method group — so a uniform-policy composite runs the byte-identical code
path as the dedicated class (regression-tested bit-for-bit).

Stacked tensors: models built with scan-over-layers stack per-layer weights
as (L, n, m). Marking them ``stacked`` makes compression vmap over L,
preserving per-layer low-rank structure (equivalent to per-layer PowerSGD in
an unrolled network).
"""
from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core.comm import AxisComm, CommRecord
from repro.core.low_rank import matricize_shape

__all__ = [
    "CompressorConfig",
    "LeafPolicy",
    "LeafPlan",
    "LeafGroupHandler",
    "TopKHandler",
    "QSGDHandler",
    "GradCompressor",
    "NoCompression",
    "TopKCompressor",
    "QSGDCompressor",
    "make_compressor",
    "build_plans",
    "POLICY_METHODS",
]

PyTree = Any

# every method a LeafPolicy may name; 'raw' is the uncompressed fp32 pmean
POLICY_METHODS = ("raw", "topk", "qsgd", "powersgd", "lq_sgd")


@dataclasses.dataclass(frozen=True)
class CompressorConfig:
    """Config shared by all compressors (subclasses add fields)."""

    name: str = "none"
    # low-rank options (powersgd / lq_sgd)
    rank: int = 1
    # quantization options (lq_sgd / qsgd)
    bits: int = 8
    bits_q: int | None = None  # paper allows b_p != b_q; None -> same as bits
    alpha: float = 10.0
    # topk options
    topk_ratio: float = 0.01
    # routing
    min_compress_numel: int = 1024
    # wire-accounting mode: 'allgather_codes' (exact packed wire) or
    # 'psum_sim' (ring-all-reduce simulation over fp32 codes). Renamed
    # from `wire` (PR 9 overloaded that word: the CLI --wire means
    # topology); the old kwarg/attribute still works but warns.
    wire_accounting: str = "allgather_codes"
    # wire-codec backend: 'jnp_ref' (pure jnp) or 'pallas' (TPU kernels,
    # interpret-mode on the CPU) — see repro.core.codec
    quant_backend: str = "jnp_ref"
    # wire codec override for the log-quant family: None -> 'log'
    # deterministic (or 'dlog' when dp_epsilon > 0), or any registered
    # log-grid codec name ('dlog', 'lrq') — see repro.core.codec
    codec: str | None = None
    # per-use differential-privacy budget for randomized codecs: > 0
    # calibrates the dlog codec's Gaussian noise to (dp_epsilon, dp_delta)
    # per transmitted message (repro.core.privacy.accounting composes
    # across steps); 0 = no DP noise
    dp_epsilon: float = 0.0
    dp_delta: float = 1e-5
    # layer count for the 'lrq' layered randomized quantizer
    lrq_layers: int = 2
    # 'paper' = dequant(mean(codes))  [Algorithm 1 literal]
    # 'dequant_then_mean' = mean(dequant(codes))  [beyond-paper ablation]
    avg_mode: str = "paper"
    # fuse all factor payloads into one flat collective (beyond-paper perf)
    fuse_collectives: bool = False
    # error-feedback storage dtype ('float32' faithful; 'bfloat16' halves the
    # dominant per-device state at >=70B scale — beyond-paper, ablated)
    state_dtype: str = "float32"
    # ---- per-leaf policies (repro.core.policy / repro.core.composite) ----
    # None/'uniform': cfg.name everywhere (the paper's global config);
    # 'auto': the cost-model planner picks per-leaf methods under
    # `error_budget`; anything else is parsed as a policy spec string
    # 'pattern=method:knob=v:...,pattern=...' (README "Per-leaf policies").
    policy: str | None = None
    error_budget: float = 0.3
    # schedule: full-precision warm-up for the first W steps (in-graph,
    # selected on the compressor state's own step counter)
    warmup_steps: int = 0
    # schedule: piecewise-constant decay caps ((start_step, rank_cap|None,
    # bits_cap|None), ...) applied by rebuilding at phase boundaries
    schedule_decay: tuple[tuple[int, int | None, int | None], ...] = ()
    # ---- lazy aggregation (repro.core.lazy) ------------------------------
    # LAQ-style skip-round gating: a method group whose accumulated
    # innovation is small contributes its cached aggregate instead of
    # firing its collectives. 0.0 = eager (bit-for-bit the non-lazy path);
    # > 0 routes through the CompositeCompressor.
    lazy_thresh: float = 0.0
    # max consecutive skipped rounds before a fire is forced (>= 1 when
    # lazy_thresh > 0 — no group may silently freeze)
    max_stale: int = 4
    # skip-round dispatch: 'elide' routes each lazy group's handler sync
    # through lax.cond on the (worker-uniform) fire predicate so a skipped
    # round's collectives are absent from the compiled program; 'gate' is
    # the legacy trace-always, where-select path (bit-identical — kept as
    # the benchmark baseline)
    lazy_mode: str = "elide"
    # adaptive LAQ: > 0 caps the threshold scaling driven by the
    # parameter-drift EMA (tau_eff^2 <= lazy_adaptive * tau^2); 0 = fixed
    # thresholds
    lazy_adaptive: float = 0.0
    # ---- wire topology (repro.core.wire) ---------------------------------
    # 'symmetric': all-reduce among peers (bit-for-bit the historical
    # path); 'server': parameter-server round — per-worker participation
    # draw, masked gather, weighted server-side aggregation, per-worker
    # lazy decisions (the group-consensus psum is replaced by local tests)
    topology: str = "symmetric"
    # server wire: each worker's independent per-round upload probability
    # (1.0 = full participation, the eager-equivalent case); < 1 routes
    # through the CompositeCompressor (per-worker state freezing + the
    # step counter the participation draw folds in)
    participation: float = 1.0
    # server aggregation weighting: 'participation' (divide by the number
    # of participants) or 'sparsity' (FedDropoutAvg per-element nonzero
    # mask — sparse TopK uploads don't dilute each other)
    agg: str = "participation"
    participation_seed: int = 0
    # ---- deprecated spellings (shims; do not add fields below) -----------
    # pre-PR-10 name of wire_accounting
    wire: dataclasses.InitVar[str | None] = None

    def __post_init__(self, wire: str | None):
        # dataclasses.replace() forwards this InitVar via getattr — i.e.
        # through the read shim below, which tags its value. A tagged value
        # is a round-trip, NOT a user override: wire_accounting (always in
        # replace()'s changes) is already authoritative, and applying the
        # stale copy here would clobber replace(cfg, wire_accounting=...).
        if wire is not None and not isinstance(wire, _ShimWire):
            warnings.warn(
                "CompressorConfig(wire=...) is deprecated; the field is now "
                "wire_accounting= (the `wire` word now means topology, as in "
                "the --wire CLI flag)", DeprecationWarning, stacklevel=3)
            object.__setattr__(self, "wire_accounting", wire)
        if self.dp_epsilon < 0:
            raise ValueError(f"dp_epsilon must be >= 0, got {self.dp_epsilon}")


class _ShimWire(str):
    """Marker for values read back through the deprecated ``.wire``
    property (compares/behaves as a plain str)."""


def _cfg_wire_shim(self: CompressorConfig) -> str:
    # silent read-compat: the deprecation warning fires on the WRITE path
    # (constructing with wire=...) — warning here would fire spuriously on
    # every dataclasses.replace(), which getattrs all init fields
    return _ShimWire(self.wire_accounting)


# a dataclass field named `wire` and a property can't coexist in the class
# body; attach the deprecated read-path after the fact
CompressorConfig.wire = property(_cfg_wire_shim)  # type: ignore[attr-defined]


@dataclasses.dataclass(frozen=True)
class LeafPolicy:
    """Per-tensor compression decision: which method ships this leaf, and
    with what knobs. Dedicated compressors use one uniform policy; the
    composite carries one per leaf."""

    method: str = "lq_sgd"   # one of POLICY_METHODS
    rank: int = 1
    bits: int = 8
    bits_q: int | None = None   # factor-Q wire bits; None -> same as bits
    topk_ratio: float = 0.01
    # wire codec for the log-quant family: None -> cfg default ('log', or
    # 'dlog' when a dp budget is set); 'dlog'/'lrq' pick the randomized
    # codecs from the registry (repro.core.codec.make_codec)
    codec: str | None = None
    # per-use DP budget for this leaf's randomized codec; 0 -> cfg default
    dp_epsilon: float = 0.0
    min_numel: int | None = None  # per-leaf routing-threshold override
    # lazy aggregation (repro.core.lazy): relative innovation threshold
    # (0.0 = eager) and the max consecutive skips before a forced fire
    lazy_thresh: float = 0.0
    max_stale: int = 4
    # adaptive LAQ: cap on the drift-EMA threshold scaling (tau_eff^2 <=
    # lazy_adaptive * tau^2); 0.0 = fixed thresholds, otherwise >= 1
    lazy_adaptive: float = 0.0

    def __post_init__(self):
        if self.method not in POLICY_METHODS:
            raise ValueError(
                f"unknown policy method {self.method!r}; options: {POLICY_METHODS}")
        if self.lazy_thresh < 0:
            raise ValueError(f"lazy_thresh must be >= 0, got {self.lazy_thresh}")
        if self.lazy_thresh > 0 and self.max_stale < 1:
            raise ValueError(
                f"lazy_thresh > 0 needs max_stale >= 1 (a staleness cap so "
                f"no group silently freezes), got max_stale={self.max_stale}")
        if self.lazy_adaptive != 0 and self.lazy_adaptive < 1:
            raise ValueError(
                f"lazy_adaptive is a scaling CAP: 0 (off) or >= 1, got "
                f"{self.lazy_adaptive}")
        if self.dp_epsilon < 0:
            raise ValueError(f"dp_epsilon must be >= 0, got {self.dp_epsilon}")
        if self.codec is not None:
            from repro.core.codec import available_codecs
            if self.codec not in available_codecs():
                raise ValueError(f"unknown codec {self.codec!r}; "
                                 f"available: {available_codecs()}")

    @property
    def eff_bits_q(self) -> int:
        return self.bits if self.bits_q is None else self.bits_q


@dataclasses.dataclass(frozen=True)
class LeafPlan:
    """Static per-tensor routing decision (computed once from shapes)."""

    path: str
    shape: tuple[int, ...]
    dtype: Any
    route: str  # 'lowrank' | 'raw'
    stacked: bool  # leading dim is a scan-layer stack
    mat_shape: tuple[int, int] | None  # per-instance matricized (n, m)
    eff_rank: int
    policy: LeafPolicy = LeafPolicy()


def _numel(shape: tuple[int, ...]) -> int:
    n = 1
    for s in shape:
        n *= s
    return n


def _leaf_plan(path: str, leaf, policy: LeafPolicy, min_numel: int,
               stacked: bool) -> LeafPlan:
    shape = tuple(leaf.shape)
    dtype = leaf.dtype
    if policy.min_numel is not None:
        min_numel = policy.min_numel
    inst_shape = shape[1:] if stacked else shape
    numel = _numel(shape)
    route = "raw"
    mat = None
    eff_rank = 0
    if (policy.method != "raw" and len(inst_shape) >= 2
            and numel >= min_numel):
        n, m = matricize_shape(inst_shape)
        r = min(policy.rank, n, m)
        if n * m > r * (n + m):  # compression actually pays
            route, mat, eff_rank = "lowrank", (n, m), r
    return LeafPlan(path, shape, dtype, route, stacked, mat, eff_rank, policy)


def build_plans(abstract_grads: PyTree, rank: int = 1, min_numel: int = 1024,
                stacked: PyTree | None = None, *,
                policy: LeafPolicy | None = None,
                policies: list[LeafPolicy] | None = None
                ) -> tuple[LeafPlan, ...]:
    """One LeafPlan per flattened leaf, in tree_flatten order.

    ``policy`` applies one uniform policy; ``policies`` is a per-leaf list
    (flatten order). With neither, a uniform powersgd policy at ``rank``
    reproduces the historical shape-only routing.
    """
    leaves, treedef = jax.tree_util.tree_flatten(abstract_grads)
    paths = [jax.tree_util.keystr(kp) for kp, _ in
             jax.tree_util.tree_flatten_with_path(abstract_grads)[0]]
    if stacked is None:
        stacked_leaves = [False] * len(leaves)
    else:
        stacked_leaves = jax.tree_util.tree_flatten(stacked)[0]
        if len(stacked_leaves) != len(leaves):
            raise ValueError("`stacked` pytree does not match grads structure")
    if policies is None:
        policy = policy or LeafPolicy(method="powersgd", rank=rank)
        policies = [policy] * len(leaves)
    if len(policies) != len(leaves):
        raise ValueError(f"{len(policies)} policies for {len(leaves)} leaves")
    return tuple(
        _leaf_plan(p, l, pol, min_numel, bool(s))
        for p, l, pol, s in zip(paths, leaves, policies, stacked_leaves)
    )


def _pmean_raw(g: jax.Array, comm: AxisComm, rec: CommRecord) -> jax.Array:
    rec.add(g.size * 32, 1)  # fp32 wire, ring all-reduce payload ~ numel
    return comm.pmean(g.astype(jnp.float32)).astype(g.dtype)


def _group_by(items, keyf):
    """Insertion-ordered grouping — a uniform group stays ONE group, so the
    grouped call is byte-identical to the ungrouped one."""
    groups: dict[Any, list] = {}
    for it in items:
        groups.setdefault(keyf(it), []).append(it)
    return groups.items()


# --------------------------------------------------------------------------
# leaf-group handlers: the method-specific sync over a subset of leaves
# --------------------------------------------------------------------------

class LeafGroupHandler:
    """Method-specific sync over an arbitrary subset of the grad leaves.

    ``sync_group`` takes ``items = [(i, grad_leaf, plan), ...]`` (``i`` the
    GLOBAL flattened-leaf index) plus the full compressor state, and returns
    ``(outs, updates)`` where ``outs`` maps leaf index -> synced tensor and
    ``updates`` maps state namespace -> {str(i): new_leaf_state}.

    State contract: per-leaf state lives in namespace dicts keyed by the
    global leaf index, so multiple handlers' namespaces merge into one
    threaded state pytree (the composite's merged state) without collisions.
    Namespaces in ``param_shaped`` hold param-shaped tensors (error
    feedback) whose sharding mirrors the parameter's.
    """

    method = "raw"
    namespaces: tuple[str, ...] = ()
    param_shaped: tuple[str, ...] = ()
    needs_prng = False  # wants state['key'] / state['step'] (QSGD)

    def __init__(self, cfg: CompressorConfig):
        self.cfg = cfg

    def group_needs_prng(self, plans) -> bool:
        """Does syncing THESE plans consume PRNG state? Static handlers
        answer with the class flag; codec-driven handlers (lq_sgd) answer
        per group — a group is only charged a key when some leaf's codec
        declares ``requires_key`` (so deterministic configs keep the exact
        historical state pytree)."""
        del plans
        return self.needs_prng

    def _group_key(self, state, comm) -> jax.Array:
        """The per-worker, per-step PRNG base every randomized handler
        derives leaf keys from: fold the step counter, then this worker's
        axis index, into the shared state key. Leaf streams split off via
        ``fold_in(base, leaf_index)`` (QSGD) or
        ``fold_in(fold_in(base, leaf_index), phase)`` (factor codecs) —
        deterministic, so reruns reproduce bit-for-bit."""
        try:
            base = jax.random.fold_in(state["key"], state["step"])
        except (KeyError, TypeError) as e:
            raise KeyError(
                f"{type(self).__name__} uses a randomized codec but the "
                "state has no 'key'/'step' — build via make_compressor "
                "(the composite threads PRNG state when a group needs it)"
            ) from e
        return jax.random.fold_in(base,
                                  jax.lax.axis_index(comm.axis_names[-1]))

    # ---- per-leaf state ---------------------------------------------------
    def init_leaf_state(self, key: jax.Array, i: int, pl: LeafPlan
                        ) -> dict[str, jax.Array]:
        return {}

    # ---- the group sync ---------------------------------------------------
    def sync_raw(self, g: jax.Array, pl: LeafPlan, comm: AxisComm,
                 rec: CommRecord, *, key: jax.Array | None = None) -> jax.Array:
        del key  # the fp32 pmean path is deterministic
        return _pmean_raw(g, comm, rec)

    def sync_group(self, items, state: PyTree, comm: AxisComm,
                   rec: CommRecord) -> tuple[dict[int, jax.Array], dict]:
        return ({i: self.sync_raw(g, pl, comm, rec) for i, g, pl in items},
                {})

    # ---- static accounting ------------------------------------------------
    def raw_wire_bits(self, pl: LeafPlan, numel: int) -> int:
        return numel * 32

    def leaf_wire_bits(self, pl: LeafPlan) -> int:
        return self.raw_wire_bits(pl, _numel(pl.shape))

    def leaf_physical_bits(self, pl: LeafPlan) -> int:
        """Bits the TRACED graph actually moves for this leaf in a fired
        round — what a collective-inventory walk of the jaxpr sums to, as
        opposed to ``leaf_wire_bits``'s semantic accounting. The two
        differ exactly where a wire is *simulated* at a different width:
        TopK's dense fp32 stand-in for the sparse payload, and
        ``cfg.wire_accounting='psum_sim'`` shipping codes as fp32. The
        graph-lint accounting-parity rule checks the graph against THIS
        figure and reports where it diverges from the semantic one."""
        return self.leaf_wire_bits(pl)

    def leaf_epsilon(self, pl: LeafPlan, delta: float = 1e-5) -> float:
        """Per-step DP epsilon spent transmitting this leaf — the sum of
        ``epsilon_per_use`` over every encode the leaf's sync performs
        (``inf`` for any deterministic transmission: a fully-revealed
        message has no DP guarantee)."""
        del delta
        return math.inf


class TopKHandler(LeafGroupHandler):
    """TopK-SGD (Shi et al. 2019 / Aji & Heafield 2017) with error feedback.

    Per compressed tensor: keep the top-k entries by magnitude of the
    error-corrected gradient, zero the rest; the dense masked tensor is
    pmean'd (the standard dense simulation of sparse all-reduce) while wire
    accounting charges k * (32-bit value + ceil(log2(numel))-bit index) per
    worker — the honest sparse payload (an index into numel slots never
    needs a flat 32 bits).
    """

    method = "topk"
    namespaces = ("err",)
    param_shaped = ("err",)

    @staticmethod
    def _k(numel: int, ratio: float) -> int:
        return max(1, int(numel * ratio))

    @staticmethod
    def index_bits(numel: int) -> int:
        """Bits to address one of ``numel`` slots on the sparse wire."""
        return max(1, math.ceil(math.log2(numel))) if numel > 1 else 1

    def init_leaf_state(self, key, i, pl):
        if pl.route != "lowrank":  # reuse routing: 'compressible'
            return {}
        return {"err": jnp.zeros(pl.shape, jnp.dtype(self.cfg.state_dtype))}

    def sync_group(self, items, state, comm, rec):
        from repro.core.codec import codec_phase, make_codec
        outs: dict[int, jax.Array] = {}
        new_err: dict[str, jax.Array] = {}
        comp, kepts, account = [], [], []
        for i, g, pl in items:
            if pl.route != "lowrank":
                outs[i] = self.sync_raw(g, pl, comm, rec)
                continue
            e = state["err"][str(i)]
            g32 = g.astype(jnp.float32) + e.astype(jnp.float32)
            flat = g32.reshape(-1)
            k = self._k(flat.size, pl.policy.topk_ratio)
            vals, idx = jax.lax.top_k(jnp.abs(flat), k)
            mask = jnp.zeros_like(flat).at[idx].set(1.0)
            kept = flat * mask
            new_err[str(i)] = (flat - kept).reshape(pl.shape).astype(
                jnp.dtype(self.cfg.state_dtype))
            comp.append((i, g, pl))
            kepts.append(kept.reshape(pl.shape))
            account.append(k * (32 + self.index_bits(flat.size)))
        if comp:
            # dense simulation of the sparse all-reduce through the fp32
            # codec; accounting charges the k*(32+idx)-bit sparse payload
            synced = codec_phase(kepts, [pl.stacked for _, _, pl in comp],
                                 make_codec("float32"), comm, rec,
                                 avg_mode=self.cfg.avg_mode,
                                 wire=self.cfg.wire_accounting,
                                 fuse=self.cfg.fuse_collectives,
                                 account_bits=account)
            for (i, g, pl), s in zip(comp, synced):
                outs[i] = s.astype(g.dtype)
        return outs, {"err": new_err}

    def leaf_wire_bits(self, pl):
        numel = _numel(pl.shape)
        if pl.route != "lowrank":
            return self.raw_wire_bits(pl, numel)
        return (self._k(numel, pl.policy.topk_ratio)
                * (32 + self.index_bits(numel)))

    def leaf_physical_bits(self, pl):
        numel = _numel(pl.shape)
        if pl.route != "lowrank":
            return self.raw_wire_bits(pl, numel)
        # the dense fp32 simulation of the sparse all-reduce ships the
        # whole masked tensor regardless of wire mode
        return numel * 32


class QSGDHandler(LeafGroupHandler):
    """QSGD (Alistarh et al. 2017): stochastic uniform quantization.

    Derives per-worker, per-tensor, per-step PRNG keys from the shared
    ``state['key']`` / ``state['step']`` (folded with the global leaf index,
    so a composite group draws the same stream as the dedicated class).
    """

    method = "qsgd"
    needs_prng = True

    def _codec(self, bits: int):
        from repro.core.codec import make_codec
        return make_codec("qsgd", bits=bits, backend=self.cfg.quant_backend)

    def sync_group(self, items, state, comm, rec):
        from repro.core.codec import codec_phase
        # per-worker, per-step base; leaf streams fold in the global index
        base = self._group_key(state, comm)
        outs: dict[int, jax.Array] = {}
        comp = []
        for i, g, pl in items:
            if pl.route != "lowrank":
                outs[i] = self.sync_raw(g, pl, comm, rec)
            else:
                comp.append((i, g, pl))
        # one codec == one wire dtype == one (fused) phase; per-leaf bits
        # sub-group, and a uniform group stays a single phase call
        for bits, sub in _group_by(comp, lambda it: it[2].policy.bits):
            # stochastic rounding is unbiased under plain averaging; the
            # linear QSGD codec makes both avg modes identical anyway
            synced = codec_phase(
                [g for _, g, _ in sub], [pl.stacked for _, _, pl in sub],
                self._codec(bits), comm, rec, avg_mode="dequant_then_mean",
                wire=self.cfg.wire_accounting, fuse=self.cfg.fuse_collectives,
                keys=[jax.random.fold_in(base, i) for i, _, _ in sub])
            for (i, g, pl), s in zip(sub, synced):
                outs[i] = s.astype(g.dtype)
        return outs, {}

    def leaf_wire_bits(self, pl):
        numel = _numel(pl.shape)
        if pl.route != "lowrank":
            return self.raw_wire_bits(pl, numel)
        codec = self._codec(pl.policy.bits)
        L = pl.shape[0] if pl.stacked else 1
        return codec.wire_bits(numel) + codec.scale_bits(L)

    def leaf_physical_bits(self, pl):
        numel = _numel(pl.shape)
        if pl.route != "lowrank":
            return self.raw_wire_bits(pl, numel)
        codec = self._codec(pl.policy.bits)
        L = pl.shape[0] if pl.stacked else 1
        if self.cfg.wire_accounting == "psum_sim":  # codes ride the psum as fp32
            return numel * 32 + codec.scale_bits(L)
        return codec.wire_bits(numel) + codec.scale_bits(L)


# --------------------------------------------------------------------------
# compressors: one handler driven over the whole pytree
# --------------------------------------------------------------------------

class GradCompressor:
    """Base: raw pmean for everything. Subclasses swap the handler."""

    method = "raw"
    handler_cls: type[LeafGroupHandler] = LeafGroupHandler

    def __init__(self, cfg: CompressorConfig, abstract_grads: PyTree,
                 stacked: PyTree | None = None):
        self.cfg = cfg
        self.treedef = jax.tree_util.tree_structure(abstract_grads)
        policy = LeafPolicy(method=self.method, rank=cfg.rank, bits=cfg.bits,
                            bits_q=cfg.bits_q, topk_ratio=cfg.topk_ratio,
                            codec=cfg.codec, dp_epsilon=cfg.dp_epsilon)
        self.plans = build_plans(abstract_grads, cfg.rank,
                                 cfg.min_compress_numel, stacked,
                                 policy=policy)
        self.handler = self.handler_cls(cfg)

    # ---- state -----------------------------------------------------------
    def init_state(self, key: jax.Array) -> PyTree:
        state: dict[str, Any] = {ns: {} for ns in self.handler.namespaces}
        for i, pl in enumerate(self.plans):
            for ns, v in self.handler.init_leaf_state(key, i, pl).items():
                state[ns][str(i)] = v
        return state

    @staticmethod
    def _merge_state(state: PyTree, updates: dict) -> PyTree:
        if not updates:
            return state
        new = dict(state)
        for ns, sub in updates.items():
            cur = dict(state.get(ns, {}))
            cur.update(sub)
            new[ns] = cur
        return new

    # ---- the wire --------------------------------------------------------
    def _make_wire(self, comm: AxisComm, state: PyTree):
        """The configured wire over ``comm`` (bare AxisComm callers land on
        the symmetric path; an already-wrapped wire passes through). The
        server wire folds the state's step counter into its participation
        draw so the drop-out pattern varies over the run."""
        from repro.core.wire import as_wire
        step = state.get("step") if isinstance(state, dict) else None
        return as_wire(comm, topology=self.cfg.topology,
                       participation=self.cfg.participation,
                       agg=self.cfg.agg, seed=self.cfg.participation_seed,
                       step=step)

    def _freeze_inactive(self, updates: dict, state: PyTree, wire) -> dict:
        """Server wire with drop-out: a worker that sat the round out never
        uploaded, so its per-worker error feedback must not advance.
        Collective-derived state (warm Q, PRNG counters) is worker-
        identical and advances for everyone."""
        if (wire.kind != "server"
                or getattr(wire, "participation", 1.0) >= 1.0):
            return updates
        act = wire.active()
        for ns in self._param_shaped_namespaces():
            sub = updates.get(ns)
            if not sub:
                continue
            for k, v in sub.items():
                old = state.get(ns, {}).get(k)
                if old is not None:
                    sub[k] = jnp.where(act, v, old.astype(v.dtype))
        return updates

    def _charge_downlink(self, rec: CommRecord, wire) -> None:
        """Server rounds end with the server broadcasting the dequantized
        fp32 aggregate — downlink bookkeeping, separate from the uplink
        headline (the symmetric all-reduce has no broadcast leg)."""
        if wire.kind == "server":
            rec.add_down(32 * sum(_numel(pl.shape) for pl in self.plans))

    # ---- the sync op -----------------------------------------------------
    def sync(self, grads: PyTree, state: PyTree, comm: AxisComm
             ) -> tuple[PyTree, PyTree, CommRecord]:
        rec = CommRecord()
        wire = self._make_wire(comm, state)
        # participation sideband charges OUTSIDE the per-method scopes so
        # the analysis accounting-parity buckets stay exact per method
        wire.prepare(rec)
        leaves = jax.tree_util.tree_flatten(grads)[0]
        items = list(zip(range(len(leaves)), leaves, self.plans))
        # same source tag the composite puts on its eager groups, so the
        # graph-lint inventory maps collectives to methods either way
        with jax.named_scope(f"comp.{self.method}.eager"):
            outs, updates = self.handler.sync_group(items, state, wire, rec)
        updates = self._freeze_inactive(updates, state, wire)
        self._charge_downlink(rec, wire)
        out = [outs[i] for i in range(len(leaves))]
        return (jax.tree_util.tree_unflatten(self.treedef, out),
                self._merge_state(state, updates), rec)

    def sync_once(self, grads: PyTree, state: PyTree,
                  axis_name: str = "solo") -> tuple[PyTree, PyTree, CommRecord]:
        """Single-worker ``sync``: wraps the named-axis collectives in a
        size-1 ``vmap`` axis so callers (the GIA harness, demos, notebooks)
        don't hand-roll the wrapper. The compression is still lossy — the
        output is the reconstruction an eavesdropper observes on the wire.
        Returns ``(synced, new_state, CommRecord)`` with batch dims stripped;
        ``new_state`` MUST be threaded into the next call for error feedback
        and warm-start Q to evolve as they do in training."""
        recs: list[CommRecord] = []

        def one(g, st):
            out, st2, rec = self.sync(g, st, AxisComm((axis_name,)))
            recs.append(rec)
            return out, st2

        g1 = jax.tree.map(lambda t: t[None], grads)
        st1 = jax.tree.map(lambda t: t[None], state)
        out, st2 = jax.vmap(one, axis_name=axis_name)(g1, st1)
        strip = lambda tr: jax.tree.map(lambda t: t[0], tr)
        return strip(out), strip(st2), recs[0]

    # ---- sharding of per-worker state over the tensor-parallel axis ------
    def _param_shaped_namespaces(self) -> tuple[str, ...]:
        return self.handler.param_shaped

    def state_pspecs(self, state: PyTree, param_pspecs: PyTree, dp_axes):
        """PartitionSpecs for ``state`` leaves (WITHOUT the leading DP dim —
        the train step prepends it), as a structured
        ``{namespace: {leaf_index: spec}}`` mapping. Namespaces the handler
        declares ``param_shaped`` (error feedback) hold param-shaped tensors
        keyed by the global flattened leaf index and mirror that parameter's
        model-axis sharding; every other leaf replicates."""
        from jax.sharding import PartitionSpec as P
        pspecs_flat = jax.tree_util.tree_flatten(
            param_pspecs, is_leaf=lambda x: isinstance(x, P))[0]
        param_ns = set(self._param_shaped_namespaces())
        rep = lambda leaf: P(*([None] * leaf.ndim))
        specs: dict[str, Any] = {}
        for ns, sub in state.items():
            if ns in param_ns and isinstance(sub, dict):
                specs[ns] = {k: pspecs_flat[int(k)] for k in sub}
            else:
                specs[ns] = jax.tree.map(rep, sub)
        return specs

    # ---- helpers ---------------------------------------------------------
    def _raw_sync(self, g: jax.Array, comm: AxisComm, rec: CommRecord) -> jax.Array:
        return _pmean_raw(g, comm, rec)

    # static accounting for tables -----------------------------------------
    def wire_bits_per_step(self) -> int:
        return sum(self.handler.leaf_wire_bits(pl) for pl in self.plans)

    def physical_bits_by_method(self) -> dict[str, int]:
        """Traced-graph traffic per method group (one group here; the
        composite overrides with its per-method split). What the
        graph-lint accounting-parity rule sums the inventory against."""
        return {self.method: sum(self.handler.leaf_physical_bits(pl)
                                 for pl in self.plans)}

    def privacy_epsilon_per_step(self, delta: float = 1e-5) -> float:
        """Per-step DP epsilon under basic composition over every leaf's
        transmissions. ``inf`` as soon as ANY leaf ships deterministically
        (one fully-revealed tensor voids the step's guarantee). Compose
        across steps with ``repro.core.privacy.accounting``."""
        return sum(self.handler.leaf_epsilon(pl, delta) for pl in self.plans)

    def privacy_budget(self, steps: int, *, delta: float = 1e-5,
                       sampling_rate: float = 1.0):
        """End-of-training :class:`~repro.core.privacy.accounting.
        TrainingBudget` for a ``steps``-step run of this compressor."""
        from repro.core.privacy.accounting import compose_training
        return compose_training(self.privacy_epsilon_per_step(delta), steps,
                                delta=delta, sampling_rate=sampling_rate)


class NoCompression(GradCompressor):
    """Vanilla distributed SGD: full-precision all-reduce (paper 'Original SGD')."""


class TopKCompressor(GradCompressor):
    """TopK-SGD driven over the whole pytree — see :class:`TopKHandler`."""

    method = "topk"
    handler_cls = TopKHandler


class QSGDCompressor(GradCompressor):
    """QSGD baseline driven over the whole pytree — see :class:`QSGDHandler`.

    Included as an extra quantization baseline (the paper cites it as the
    canonical uniform scheme that log-quantization improves upon for
    heavy-tailed gradients).
    """

    method = "qsgd"
    handler_cls = QSGDHandler

    def init_state(self, key: jax.Array) -> PyTree:
        return {"key": key, "step": jnp.zeros((), jnp.int32)}

    def sync(self, grads, state, comm):
        out, new_state, rec = super().sync(grads, state, comm)
        # advance the PRNG stream: without this, every sync re-draws the
        # SAME stochastic rounding (regression-tested)
        new_state = dict(new_state)
        new_state["step"] = state["step"] + 1
        return out, new_state, rec


def make_compressor(cfg: CompressorConfig, abstract_grads: PyTree,
                    stacked: PyTree | None = None) -> GradCompressor:
    # local imports avoid a cycle (powersgd/lq_sgd import this module)
    from repro.core.powersgd import PowerSGDCompressor
    from repro.core.lq_sgd import LQSGDCompressor

    if cfg.topology not in ("symmetric", "server"):
        raise ValueError(f"unknown topology {cfg.topology!r}; options: "
                         "'symmetric', 'server'")
    # server drop-out needs the composite: it owns the step counter the
    # participation draw folds in and the per-worker state freezing
    server_dropout = cfg.topology == "server" and cfg.participation < 1.0
    # randomized codecs need the composite too: it owns the state
    # 'key'/'step' pair the per-leaf PRNG streams derive from
    randomized = cfg.dp_epsilon > 0 or cfg.codec is not None
    if (cfg.policy not in (None, "uniform") or cfg.warmup_steps
            or cfg.schedule_decay or cfg.lazy_thresh > 0 or server_dropout
            or randomized):
        from repro.core.composite import CompositeCompressor, PolicySchedule
        from repro.core.policy import plan_auto, resolve_policies
        report = None
        if cfg.policy == "auto":
            # plan once; stash the report so launchers print the exact
            # plan in force instead of re-running the planner
            policies, report = plan_auto(abstract_grads, stacked, cfg=cfg)
        else:
            policies = resolve_policies(cfg, abstract_grads, stacked)
        schedule = PolicySchedule(warmup_steps=cfg.warmup_steps,
                                  decay=cfg.schedule_decay)
        comp = CompositeCompressor(cfg, abstract_grads, stacked,
                                   policies=policies, schedule=schedule)
        comp.plan_report = report
        return comp

    registry: dict[str, Callable[..., GradCompressor]] = {
        "none": NoCompression,
        "sgd": NoCompression,
        "topk": TopKCompressor,
        "qsgd": QSGDCompressor,
        "powersgd": PowerSGDCompressor,
        "lq_sgd": LQSGDCompressor,
    }
    if cfg.name not in registry:
        raise ValueError(f"unknown compressor {cfg.name!r}; options: {sorted(registry)}")
    return registry[cfg.name](cfg, abstract_grads, stacked)
