"""Analytic cost model for TPU collectives and data-parallel scaling.

Reference: upstream DL4J justifies its gradient-sharing design with
measured Ethernet allreduce costs (Strom 2015 threshold encoding in
`SharedTrainingMaster`); there is no analytic model — scaling claims are
empirical Spark runs. On TPU the interconnect is regular (2D/3D torus
ICI inside a slice, DCN between slices), so collective time is
predictable from first principles; this module implements the standard
ring/torus model (as popularized by the public "How to Scale Your
Model" book) and uses it to *prove* the SURVEY §6 claim — ≥80% scaling
efficiency from 8 to 128 chips for the flagship ResNet-50 config —
without needing 128 physical chips.

Model (bandwidth term + latency term, per mesh axis):

  all_gather(D bytes, axis N, bw W)      = D*(N-1)/N / W  +  (N-1)*t_hop
  reduce_scatter                          = same as all_gather
  all_reduce                              = 2 * all_gather  (RS + AG)
  ppermute (neighbor shift)               = D / W_link      +  t_hop

where W is the *bidirectional* bandwidth available to the axis (a torus
ring sends both ways), multiplied across mesh axes when XLA splits the
collective over several ICI dimensions.  DCN-crossing collectives use
the per-chip DCN share instead of ICI.
"""

from __future__ import annotations

from dataclasses import dataclass, field


_HOP_LATENCY_S = 1e-6  # per-hop ICI latency floor (~1 us)
_DCN_LATENCY_S = 10e-6  # per-round DCN latency floor


@dataclass(frozen=True)
class ChipSpec:
    """Public headline specs for one TPU generation (per chip)."""

    name: str
    bf16_flops: float            # peak bf16 FLOP/s
    hbm_bytes_per_s: float       # HBM bandwidth
    ici_link_bytes_per_s: float  # ONE-way bandwidth of one ICI link
    ici_torus_axes: int          # 2 => 2D torus (v5e), 3 => 3D (v4/v5p)
    dcn_bytes_per_s: float       # per-CHIP share of host DCN bandwidth
    max_slice_chips: int         # pod/slice size before DCN is required


CHIPS = {
    "v5e": ChipSpec("v5e", bf16_flops=197e12, hbm_bytes_per_s=819e9,
                    ici_link_bytes_per_s=45e9, ici_torus_axes=2,
                    dcn_bytes_per_s=6.25e9, max_slice_chips=256),
    "v5p": ChipSpec("v5p", bf16_flops=459e12, hbm_bytes_per_s=2765e9,
                    ici_link_bytes_per_s=90e9, ici_torus_axes=3,
                    dcn_bytes_per_s=6.25e9, max_slice_chips=8960),
    "v4": ChipSpec("v4", bf16_flops=275e12, hbm_bytes_per_s=1228e9,
                   ici_link_bytes_per_s=45e9, ici_torus_axes=3,
                   dcn_bytes_per_s=6.25e9, max_slice_chips=4096),
}


def _axis_bw(chip: ChipSpec, n_ici_axes: int) -> float:
    """Bidirectional bandwidth a collective can drive when XLA spreads it
    over `n_ici_axes` torus dimensions (each axis = one link pair)."""
    n = max(1, min(n_ici_axes, chip.ici_torus_axes))
    return 2.0 * chip.ici_link_bytes_per_s * n


def all_gather_time(nbytes: float, axis_size: int, chip: ChipSpec, *,
                    n_ici_axes: int = 1, dcn: bool = False) -> float:
    """Time to all-gather an array whose FULL (gathered) size is `nbytes`
    over a mesh axis of `axis_size` devices."""
    if axis_size <= 1:
        return 0.0
    if dcn:
        bw = chip.dcn_bytes_per_s
        hops = axis_size - 1
        lat = _DCN_LATENCY_S
    else:
        bw = _axis_bw(chip, n_ici_axes)
        # splitting over k torus axes also splits the ring: each axis
        # carries a ring of ~N^(1/k) devices, traversed concurrently, so
        # the latency chain is k*(N^(1/k)-1) hops, not N-1
        k = max(1, min(n_ici_axes, chip.ici_torus_axes))
        hops = k * (axis_size ** (1.0 / k) - 1.0)
        lat = _HOP_LATENCY_S
    frac = (axis_size - 1) / axis_size
    return nbytes * frac / bw + hops * lat


def reduce_scatter_time(nbytes, axis_size, chip, *, n_ici_axes=1,
                        dcn=False):
    return all_gather_time(nbytes, axis_size, chip, n_ici_axes=n_ici_axes,
                           dcn=dcn)


def all_reduce_time(nbytes, axis_size, chip, *, n_ici_axes=1, dcn=False):
    """psum = reduce-scatter + all-gather (the bandwidth-optimal lowering
    XLA uses); 2x the one-pass cost, independent of axis size for large N."""
    return 2.0 * all_gather_time(nbytes, axis_size, chip,
                                 n_ici_axes=n_ici_axes, dcn=dcn)


def ppermute_time(nbytes, chip, *, dcn=False):
    """One neighbor-to-neighbor shift (ring attention / pipeline stage
    handoff): pure point-to-point over a single link."""
    if dcn:
        return nbytes / chip.dcn_bytes_per_s + _DCN_LATENCY_S
    return nbytes / chip.ici_link_bytes_per_s + _HOP_LATENCY_S


@dataclass
class DataParallelModel:
    """Scaling model for the psum gradient-sharing trainer
    (`parallel.trainer`): per-step compute time is constant per replica
    (batch-per-chip fixed — weak scaling), communication is one gradient
    all-reduce, partially overlapped with the backward pass.

    `overlap` is the fraction of allreduce time hidden under backprop
    compute: XLA's latency-hiding scheduler starts layer-k's grad
    reduction while layer k-1's backward runs. The default 0.63 is
    MEASURED, not assumed: parallel/overlap.py compiles the flagship
    ResNet-50 DP step and reads the schedule — 151 per-layer grad
    all-reduces interleaved through the backward, payload-weighted
    compute-after fraction 0.626 (big early-layer grads finish last and
    have the least compute behind them, which is why it is not ~1.0).
    """

    step_time_s: float           # measured single-chip train-step time
    grad_bytes: float            # bytes all-reduced per step
    chip: ChipSpec = field(default_factory=lambda: CHIPS["v5e"])
    overlap: float = 0.63        # measured: parallel/overlap.py
    compression: float = 1.0     # 1.0 = dense bf16/fp32; 0.25 = int8-of-fp32

    def comm_time(self, n_chips: int) -> float:
        nbytes = self.grad_bytes * self.compression
        in_slice = min(n_chips, self.chip.max_slice_chips)
        t = all_reduce_time(nbytes, in_slice, self.chip,
                            n_ici_axes=self.chip.ici_torus_axes)
        n_slices = -(-n_chips // self.chip.max_slice_chips)
        if n_slices > 1:
            # hierarchical: ICI allreduce inside each slice, then a
            # cross-slice allreduce of the already-reduced grads over DCN
            t += all_reduce_time(nbytes, n_slices, self.chip, dcn=True)
        return t

    def step_time(self, n_chips: int) -> float:
        exposed = max(0.0, self.comm_time(n_chips) * (1.0 - self.overlap))
        return self.step_time_s + exposed

    def efficiency(self, n_chips: int, base_chips: int = 1) -> float:
        """Throughput per chip at n_chips relative to base_chips."""
        return self.step_time(base_chips) / self.step_time(n_chips)

    def report(self, chip_counts=(1, 8, 16, 32, 64, 128, 256, 512)):
        return {
            n: {
                "step_ms": round(self.step_time(n) * 1e3, 3),
                "comm_ms": round(self.comm_time(n) * 1e3, 3),
                "efficiency_vs_1": round(self.efficiency(n), 4),
            }
            for n in chip_counts
        }


def layer_step_flops(param_count, out_shape, out_kind="feedforward"):
    """Forward-pass FLOP estimate for one layer from its parameter count
    and internal output shape (leading batch dim included).

    Every parameter of a dense/conv/recurrent layer participates in one
    multiply-accumulate per output POSITION (spatial site / time step /
    single vector), so flops ~= 2 * params * batch * positions:
      FF   [B, N]          -> positions = 1
      CNN  [B, H, W, C]    -> positions = H * W
      CNN3D[B, D, H, W, C] -> positions = D * H * W
      RNN  [B, F, T]       -> positions = T
    Parameterless layers (pooling, activation) cost ~0 by this model —
    correct at the granularity the pipeline-balance report needs, where
    matmul/conv FLOPs dominate by orders of magnitude. The backward pass
    is a constant ~2x of this everywhere, so SKEW ratios are unaffected.
    """
    if not param_count or not out_shape or len(out_shape) < 2:
        return 0
    batch = out_shape[0] or 1
    if out_kind == "recurrent":
        positions = out_shape[2] if len(out_shape) > 2 and out_shape[2] else 1
    else:
        # trailing dim is the feature/channel width in every internal
        # layout (FF [B,N], CNN NHWC, CNN3D NDHWC)
        positions = 1
        for d in out_shape[1:-1]:
            positions *= d or 1
    return int(2 * param_count * batch * positions)


def resnet50_scaling(step_time_s: float = 0.0546,
                     param_count: int = 25_610_216,
                     grad_dtype_bytes: int = 2,
                     chip: str = "v5e",
                     compression: float = 1.0) -> dict:
    """The SURVEY §6 proof obligation: flagship ResNet-50 DP scaling.

    Defaults are a pre-PR-1 builder capture of the step time (batch
    128 bf16 on a v5e; not re-measured on the current code) and the
    bf16 gradient size the trainer all-reduces.
    """
    m = DataParallelModel(step_time_s=step_time_s,
                          grad_bytes=param_count * grad_dtype_bytes,
                          chip=CHIPS[chip], compression=compression)
    rep = m.report()
    rep["efficiency_8_to_128"] = round(
        m.step_time(8) / m.step_time(128), 4)
    return rep
