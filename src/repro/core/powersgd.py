"""PowerSGD (Vogels et al., NeurIPS 2019) — the paper's primary baseline.

Warm-started single power iteration with error feedback:

    G' = G + E ;  P = G'Q ;  allreduce(P) ;  P^ = orth(P)
    Q  = G'^T P^ ;  allreduce(Q) ;  G^ = P^ Q^T ;  E = G' - G^

The math lives in :class:`PowerSGDHandler`, a leaf-group handler
(:mod:`repro.core.compressors`) that syncs an arbitrary subset of the grad
leaves — the dedicated :class:`PowerSGDCompressor` drives it over every
leaf; the composite drives it over its powersgd group. Both factor phases
ship through the wire-codec layer (:func:`repro.core.codec.codec_phase`):
PowerSGD uses the fp32 :class:`~repro.core.codec.Float32Codec`; LQ-SGD
subclasses the handler and swaps in the b-bit log-quant family (possibly
randomized — see ``_leaf_codec``) — control flow is shared, only the
codec choice differs. Per-leaf ranks come from each plan's
:class:`~repro.core.compressors.LeafPolicy`; per-leaf wire bits sub-group a
phase by codec (a uniform group stays ONE fused collective per phase).
With ``cfg.fuse_collectives=True`` each phase's per-tensor gathers batch
into ONE flat collective (2 + n_raw collectives per step, numerically
identical to the unfused path — tested). Stacked (L, n, m) tensors are
compressed per-layer via vmap — equivalent to per-layer PowerSGD in an
unrolled network.

Distributed-correctness invariants (tested):
  * warm-start Q is initialized from the SAME key on every worker, so all
    workers hold identical Q_t and the linearity mean_i(G_i' Q) = Ḡ' Q makes
    the P all-reduce exact in expectation;
  * error feedback E is per-worker (never synchronized);
  * after sync every worker holds the identical reconstruction G^.

Lazy aggregation (:mod:`repro.core.lazy`) composes from OUTSIDE this
handler, with zero handler changes: on a skipped round the composite
discards this handler's outputs and holds E and warm-start Q at their
prior values (LAQ-faithful — the skipped gradient is neither applied nor
banked; see the lazy module docstring for why banking into E
double-counts), so E and Q only evolve with rounds that actually
shipped, and a fired round is byte- and state-identical to an eager one.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.core.codec import WireCodec, codec_phase, make_codec
from repro.core.compressors import (GradCompressor, LeafGroupHandler,
                                    LeafPlan, _group_by, _numel)
from repro.core.low_rank import orthonormalize

__all__ = ["PowerSGDCompressor", "PowerSGDHandler"]

PyTree = Any

# jax.named_scope tags of Algorithm 1's low-rank phases (HLO metadata only):
# the power iteration over G (error-feedback add, P = GQ, Q = G^T P^,
# reconstruct, error-feedback write) and the orthonormalisation of P
POWER_SCOPE = "lowrank.power"
ORTH_SCOPE = "lowrank.orth"


def _mat_ops(pl: LeafPlan):
    """(to_2d, P-matmul, Q-matmul, orth, reconstruct) for a leaf plan."""
    n, m = pl.mat_shape
    if pl.stacked:
        shp = (pl.shape[0], n, m)
        return (shp,
                lambda a, b: jnp.einsum("lnm,lmr->lnr", a, b),
                lambda a, b: jnp.einsum("lnm,lnr->lmr", a, b),
                jax.vmap(orthonormalize),
                lambda p, q: jnp.einsum("lnr,lmr->lnm", p, q))
    return ((n, m),
            lambda a, b: a @ b,
            lambda a, b: a.T @ b,
            orthonormalize,
            lambda p, q: p @ q.T)


class PowerSGDHandler(LeafGroupHandler):
    """Low-rank power-iteration sync over a leaf group (fp32 factor wire)."""

    method = "powersgd"
    namespaces = ("err", "q")
    param_shaped = ("err",)

    # ---- the factor wire (overridden by LQ-SGD) --------------------------
    def _leaf_codec(self, pl: LeafPlan, bits: int) -> WireCodec:
        """The wire codec for one leaf's factor phase at ``bits`` — LQ-SGD
        overrides with the (possibly randomized) log-quant family; codecs
        compare equal across leaves with the same knobs, so phase
        sub-grouping by codec keeps a uniform group ONE fused collective."""
        del pl, bits
        return make_codec("float32")

    def _leaf_bits_p(self, pl: LeafPlan) -> int:
        return 32

    def _leaf_bits_q(self, pl: LeafPlan) -> int:
        return 32

    def _codec_p(self, pl: LeafPlan) -> WireCodec:
        return self._leaf_codec(pl, self._leaf_bits_p(pl))

    def _codec_q(self, pl: LeafPlan) -> WireCodec:
        return self._leaf_codec(pl, self._leaf_bits_q(pl))

    def _raw_needs_key(self, pl: LeafPlan) -> bool:
        """Does the raw-route path for this leaf consume PRNG? (LQ-SGD
        quantizes raw leaves too, so a randomized codec reaches them.)"""
        del pl
        return False

    def group_needs_prng(self, plans) -> bool:
        for pl in plans:
            if pl.route == "lowrank":
                if (self._codec_p(pl).requires_key
                        or self._codec_q(pl).requires_key):
                    return True
            elif self._raw_needs_key(pl):
                return True
        return False

    # ---- state -----------------------------------------------------------
    def init_leaf_state(self, key, i, pl):
        if pl.route != "lowrank":
            return {}
        n, m = pl.mat_shape
        r = pl.eff_rank
        k = jax.random.fold_in(key, i)
        if pl.stacked:
            q = jax.random.normal(k, (pl.shape[0], m, r), jnp.float32)
        else:
            q = jax.random.normal(k, (m, r), jnp.float32)
        return {"err": jnp.zeros(pl.shape, jnp.dtype(self.cfg.state_dtype)),
                "q": q}

    # ---- one collective phase, sub-grouped by wire codec ------------------
    def _phase(self, xs: list, flags: list, codecs: list[WireCodec],
               comm, rec, keys: list | None = None) -> list:
        """Ship one factor phase; leaves sub-group by codec *instance*
        (frozen dataclasses — equal knobs hash together, so a uniform
        group stays ONE fused collective). ``keys`` is per-leaf PRNG, None
        entries for deterministic codecs."""
        out: list = [None] * len(xs)
        for codec, idxs in _group_by(range(len(xs)), lambda j: codecs[j]):
            ks = None
            if keys is not None and codec.requires_key:
                ks = [keys[j] for j in idxs]
            res = codec_phase([xs[j] for j in idxs],
                              [flags[j] for j in idxs],
                              codec, comm, rec,
                              avg_mode=self.cfg.avg_mode,
                              wire=self.cfg.wire_accounting,
                              fuse=self.cfg.fuse_collectives, keys=ks)
            for j, r in zip(idxs, res):
                out[j] = r
        return out

    # ---- the group sync ---------------------------------------------------
    # phase tags for per-leaf PRNG key derivation: a leaf's P/Q/raw streams
    # must never collide (same base key, same leaf index)
    _PHASE_P, _PHASE_Q, _PHASE_RAW = 0, 1, 2

    def _leaf_key(self, base, i: int, phase: int):
        """Per-(leaf, phase) PRNG key from the group's base key, or None
        when the group carries no key (all-deterministic codecs)."""
        if base is None:
            return None
        return jax.random.fold_in(jax.random.fold_in(base, i), phase)

    def sync_group(self, items, state, comm, rec):
        outs: dict[int, jax.Array] = {}
        new_err: dict[str, jax.Array] = {}
        new_q: dict[str, jax.Array] = {}
        # derive the group base key only when some codec actually consumes
        # randomness — deterministic configs keep a key-free state dict
        base = (self._group_key(state, comm)
                if self.group_needs_prng([pl for _, _, pl in items]) else None)
        comp = []
        for i, g, pl in items:
            if pl.route == "lowrank":
                comp.append((i, g, pl))
            elif self._raw_needs_key(pl):
                outs[i] = self.sync_raw(
                    g, pl, comm, rec,
                    key=self._leaf_key(base, i, self._PHASE_RAW))
            else:
                outs[i] = self.sync_raw(g, pl, comm, rec)
        if comp:
            flags = [pl.stacked for _, _, pl in comp]
            ops = [_mat_ops(pl) for _, _, pl in comp]
            # ---- P phase ----
            g_efs, ps = [], []
            for (i, g, pl), (shp, mm_p, _, _, _) in zip(comp, ops):
                with jax.named_scope(POWER_SCOPE):
                    g_ef = (g.astype(jnp.float32).reshape(shp)
                            + state["err"][str(i)].astype(jnp.float32)
                            .reshape(shp))                        # Alg.1 l.4
                    ps.append(mm_p(g_ef, state["q"][str(i)]))     # Alg.1 l.10
                g_efs.append(g_ef)
            ps = self._phase(ps, flags,
                             [self._codec_p(pl) for _, _, pl in comp],
                             comm, rec,
                             keys=[self._leaf_key(base, i, self._PHASE_P)
                                   for i, _, _ in comp])
            # ---- orthonormalize + Q phase ----
            p_hats, qs = [], []
            for (_, mm_p, mm_q, orth, _), g_ef, p in zip(ops, g_efs, ps):
                with jax.named_scope(ORTH_SCOPE):
                    p_hat = orth(p)                               # Alg.1 l.11
                p_hats.append(p_hat)
                with jax.named_scope(POWER_SCOPE):
                    qs.append(mm_q(g_ef, p_hat))                  # Alg.1 l.15
            qs = self._phase(qs, flags,
                             [self._codec_q(pl) for _, _, pl in comp],
                             comm, rec,
                             keys=[self._leaf_key(base, i, self._PHASE_Q)
                                   for i, _, _ in comp])
            # ---- reconstruct + error feedback ----
            for (i, g, pl), (_, _, _, _, recon), g_ef, p_hat, q_new in zip(
                    comp, ops, g_efs, p_hats, qs):
                with jax.named_scope(POWER_SCOPE):
                    g_hat = recon(p_hat, q_new)                   # Alg.1 l.19
                    new_err[str(i)] = (g_ef - g_hat).reshape(pl.shape).astype(
                        jnp.dtype(self.cfg.state_dtype))          # Alg.1 l.20
                    outs[i] = g_hat.reshape(pl.shape).astype(g.dtype)
                new_q[str(i)] = q_new
        return outs, {"err": new_err, "q": new_q}

    # ----------------------------------------------------------- accounting
    def leaf_wire_bits(self, pl):
        numel = _numel(pl.shape)
        if pl.route != "lowrank":
            return self.raw_wire_bits(pl, numel)
        cp = self._codec_p(pl)
        cq = self._codec_q(pl)
        n, m = pl.mat_shape
        r = pl.eff_rank
        L = pl.shape[0] if pl.stacked else 1
        return (cp.wire_bits(L * n * r) + cp.scale_bits(L)   # P (+ scales)
                + cq.wire_bits(L * m * r) + cq.scale_bits(L))  # Q (+ scales)

    def leaf_physical_bits(self, pl):
        if pl.route != "lowrank" or self.cfg.wire_accounting != "psum_sim":
            return self.leaf_wire_bits(pl)
        # psum_sim ships both factors' codes as fp32 (scale pmaxes as-is)
        cp = self._codec_p(pl)
        cq = self._codec_q(pl)
        n, m = pl.mat_shape
        r = pl.eff_rank
        L = pl.shape[0] if pl.stacked else 1
        return (L * n * r * 32 + cp.scale_bits(L)
                + L * m * r * 32 + cq.scale_bits(L))

    def leaf_epsilon(self, pl, delta: float = 1e-5) -> float:
        """Per-step privacy spend for one leaf: both factor phases (or the
        raw route) must be randomized, else the leaf ships in the clear
        and the spend is infinite."""
        if pl.route == "lowrank":
            return (self._codec_p(pl).epsilon_per_use(delta)
                    + self._codec_q(pl).epsilon_per_use(delta))
        return super().leaf_epsilon(pl, delta)


class PowerSGDCompressor(GradCompressor):
    """Low-rank gradient compression with error feedback + warm start."""

    method = "powersgd"
    handler_cls = PowerSGDHandler
