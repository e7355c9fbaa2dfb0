"""Configuration types for the SAFE aggregation core."""
from __future__ import annotations

import dataclasses
from typing import Optional

from repro.crypto.fixedpoint import DEFAULT_SCALE_BITS
from repro.topology import RingTopology


@dataclasses.dataclass(frozen=True)
class ChainConfig:
    """Static configuration of a secure-aggregation chain.

    Attributes:
      axis: mesh axis name the learners live on (one learner per rank).
      num_learners: chain length n (must equal the mesh axis size).
      scale_bits: fixed-point fractional bits for the ring encoding.
      mode: 'safe'  — chain with hop pads + initiator mask (paper SAFE);
            'saf'   — chain with initiator mask only, no hop pads (paper SAF);
            'insec' — plain psum of raw values (paper INSEC baseline);
            'bon'   — pairwise-mask baseline (Bonawitz et al. CCS'17).
      pipelined: False — paper-faithful sequential whole-vector chain
                 (n-1 serial hops of the full vector);
                 True  — beyond-paper rotated-initiator segment pipeline
                 (ring-reduce schedule, ~2V bytes/link; DESIGN.md §8).
      subgroups: number of parallel chains g (paper §5.5). Must divide
                 num_learners; each subgroup needs >= 3 members for the
                 paper's privacy guarantee (enforced at construction).
      weighted: carry a per-learner weight through the aggregate so the
                 published value is the weighted mean (paper §5.6).
      pod_axis: optional mesh axis for hierarchical federation (§5.10):
                 intra-pod chains then cross-pod average of group averages.
    """

    axis: str = "data"
    num_learners: int = 16
    scale_bits: int = DEFAULT_SCALE_BITS
    mode: str = "safe"
    pipelined: bool = False
    subgroups: int = 1
    weighted: bool = False
    pod_axis: Optional[str] = None

    def __post_init__(self) -> None:
        if self.mode not in ("safe", "saf", "insec", "bon"):
            raise ValueError(f"unknown mode {self.mode!r}")
        # topology construction checks divisibility; the privacy bound
        # (>= 3 members per ring, paper §5.3/§5.5) applies to the masked
        # chain modes only
        topo = RingTopology(self.num_learners, self.subgroups)
        if self.mode in ("safe", "saf"):
            topo.validate_privacy()

    @property
    def topology(self) -> RingTopology:
        """Ring geometry shared with the sim plane (repro.topology)."""
        return RingTopology(self.num_learners, self.subgroups)

    @property
    def group_size(self) -> int:
        return self.num_learners // self.subgroups


@dataclasses.dataclass(frozen=True)
class RoundKeys:
    """Per-round key material (host-provisioned, device-resident).

    provisioning_seed: uint32[2] master seed from which pairwise hop keys
      are derived (models the out-of-band Round-0 exchange; DESIGN.md §6).
    learner_seed: uint32[2] per-learner private seed (initiator mask R and
      BON self-mask b_i are keystreams from it).
    counter_base: first fresh counter word for this round (host-allocated
      via ``crypto.prf.RoundCounter`` so pads are never reused).
    """

    provisioning_seed: object  # jax.Array uint32[2]
    learner_seed: object  # jax.Array uint32[2] (per-rank, distinct)
    counter_base: object  # jax.Array uint32 scalar or int
