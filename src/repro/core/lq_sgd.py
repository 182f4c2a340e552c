"""LQ-SGD — the paper's Algorithm 1 (PowerSGD + logarithmic quantization).

Identical control flow to :class:`~repro.core.powersgd.PowerSGDHandler` —
literally the same group sync — with the factor wire swapped from fp32 to
the b-bit log-quantized :class:`~repro.core.codec.LogQuantCodec` (paper
Eq. 5/6):

    scale  = pmax_i max|x_i|                       (shared quantization grid)
    codes  = round( log1p(a|x|/s) / log1p(a) * L ) (signed b-bit integers)
    wire   = all_gather(packed codes)  or  psum-simulated ring all-reduce
    mean   = dequant(mean(codes))                  ["paper", Alg.1 literal]
           | mean(dequant(codes))                  ["dequant_then_mean"]

``cfg.quant_backend`` selects the codec backend: ``jnp_ref`` (pure jnp) or
``pallas`` (the fused TPU kernels, interpret-mode on the CPU). b<=4 codes are
nibble-packed two-per-int8, so the gathered arrays really are b/8 of the
int8 bytes — wire accounting equals actual array bytes.

Randomized wire: ``cfg.codec`` / per-leaf ``LeafPolicy.codec`` swap the
deterministic ``log`` codec for its randomized relatives (``dlog`` with a
calibrated DP budget, ``lrq`` layered-randomized — see
:mod:`repro.core.codec`); a nonzero ``dp_epsilon`` with no explicit codec
defaults to ``dlog``. Wire format and bit accounting are unchanged — only
the rounding rule is stochastic, with per-(leaf, phase) keys derived in
:class:`~repro.core.powersgd.PowerSGDHandler`.

Per-leaf bit-widths come from each plan's
:class:`~repro.core.compressors.LeafPolicy` (``bits`` for the P phase,
``bits_q`` for the Q phase — the paper allows b_p != b_q); leaves with
different bit-widths sub-group into one collective per wire dtype, and a
uniform group stays a single fused phase.

Stacked (layer-scanned) tensors quantize with per-layer scales — the exact
equivalent of per-tensor scales in an unrolled network.

Non-low-rank tensors (biases, norms — PowerSGD's 'rank-1' path) are ALSO
log-quantized to b bits before their all-reduce: this is what reconciles
the paper's Table-I LQ-SGD sizes (3 MB vs PowerSGD 14 MB = the full 32/b
on *everything*, not just factors).

Wire accounting: b bits/scalar + 32-bit scale per tensor instance, i.e.
``r(n+m)·b`` bits per compressed matrix — the paper's §IV-C claim of a
``32/b`` ratio vs PowerSGD.

Skip-round composition: LAQ-style lazy aggregation (:mod:`repro.core.
lazy`, a ``LeafPolicy.lazy_thresh`` knob) multiplies with this wire — a
fired round ships ``r(n+m)·b`` bits and most rounds ship only the 64-bit
decision sideband, with skipped updates recycled through E exactly as in
PowerSGD (see that module's docstring).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.codec import WireCodec, codec_phase, make_codec
from repro.core.compressors import GradCompressor
from repro.core.powersgd import PowerSGDHandler

__all__ = ["LQSGDCompressor", "LQSGDHandler"]


class LQSGDHandler(PowerSGDHandler):
    """See module docstring: PowerSGD control flow over a log-quantized wire."""

    method = "lq_sgd"

    def _leaf_codec(self, pl, bits: int) -> WireCodec:
        """Resolve the log-quant family member for one leaf.

        Selection: ``pl.policy.codec`` (per-leaf override from the policy /
        auto-planner) > ``cfg.codec`` > the default family — plain ``log``,
        or ``dlog`` when this leaf carries a DP budget (noise has to come
        from somewhere). Privacy knobs (``dp_epsilon``/``dp_delta``,
        ``n_layers``) ride in from the same policy/cfg pair.
        """
        eps = pl.policy.dp_epsilon or self.cfg.dp_epsilon
        name = pl.policy.codec or self.cfg.codec or (
            "dlog" if eps > 0 else "log")
        knobs = dict(bits=bits, alpha=self.cfg.alpha,
                     backend=self.cfg.quant_backend)
        if name == "dlog":
            knobs.update(dp_epsilon=eps, dp_delta=self.cfg.dp_delta)
        elif name == "lrq":
            knobs.update(n_layers=min(self.cfg.lrq_layers, max(1, bits - 1)))
        return make_codec(name, **knobs)

    def _leaf_bits_p(self, pl) -> int:
        return pl.policy.bits

    def _leaf_bits_q(self, pl) -> int:
        return pl.policy.eff_bits_q

    def _raw_codec(self, pl) -> WireCodec:
        return self._leaf_codec(pl, pl.policy.bits)

    def _raw_needs_key(self, pl) -> bool:
        return self._raw_codec(pl).requires_key

    def sync_raw(self, g, pl, comm, rec, *, key=None):
        # Algorithm 1's code-domain mean applies to the low-rank factors;
        # for raw leaves (biases/norms, sign-mixed small tensors) the
        # log-domain mean is badly biased (a quasi-geometric mean), so the
        # quantized raw path always averages dequantized values.
        codec = self._raw_codec(pl)
        out = codec_phase([g.astype(jnp.float32)], [False],
                          codec, comm, rec,
                          avg_mode="dequant_then_mean",
                          wire=self.cfg.wire_accounting,
                          fuse=False,
                          keys=[key] if codec.requires_key else None)[0]
        return out.astype(g.dtype)

    def raw_wire_bits(self, pl, numel: int) -> int:
        codec = self._raw_codec(pl)
        return codec.wire_bits(numel) + codec.scale_bits(1)

    def leaf_physical_bits(self, pl):
        if pl.route == "lowrank" or self.cfg.wire_accounting != "psum_sim":
            return super().leaf_physical_bits(pl)
        # quantized raw leaves under psum_sim: codes ride the psum as fp32
        from repro.core.compressors import _numel
        codec = self._raw_codec(pl)
        return _numel(pl.shape) * 32 + codec.scale_bits(1)

    def leaf_epsilon(self, pl, delta: float = 1e-5) -> float:
        if pl.route == "lowrank":
            return super().leaf_epsilon(pl, delta)
        return self._raw_codec(pl).epsilon_per_use(delta)


class LQSGDCompressor(GradCompressor):
    """The paper's LQ-SGD driven over the whole pytree."""

    method = "lq_sgd"
    handler_cls = LQSGDHandler
