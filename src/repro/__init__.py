"""repro — SAFE secure aggregation (Sandholm et al. 2021) as a
production-grade multi-pod JAX framework.

Public API surface:
  repro.topology  — shared ring/subgroup/hierarchical topology layer
  repro.core      — SecureAggregator (safe/saf/insec/bon), protocol sim,
                    AggSession
  repro.crypto    — Threefry PRF, fixed-point ring codec
  repro.kernels   — Pallas TPU masking kernels (+ jnp oracles)
  repro.models    — the 10-architecture zoo
  repro.configs   — get_config / get_smoke_config / all_arch_ids
  repro.train     — make_train_step, make_federated_round
  repro.serve     — ServeEngine, AggregationEngine
  repro.launch    — the mesh constructor, compile cache, train/serve CLIs

See ARCHITECTURE.md for the two-plane + topology-layer picture.
"""

__version__ = "1.0.0"
