"""Pallas kernel benchmark: fused masking vs unfused reference.

On this CPU container the kernels execute in interpret mode, so wall
time is NOT TPU-predictive. The roofline-relevant derived numbers are
static: HBM bytes per element for the fused kernel vs the unfused op
sequence, and the VPU op count of the Threefry schedule. Wall time of
the jnp oracle is reported as the correctness-path cost only.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, save_json, wall
from repro.kernels.ref import chain_combine_ref, mask_add_ref
from repro.crypto.prf import keystream_pair_lanes

V = 1 << 22  # 4M elements (a ~16 MB gradient chunk)


def run() -> dict:
    x = jnp.asarray(np.random.RandomState(0).uniform(-1, 1, V)
                    .astype(np.float32))
    cipher = jnp.asarray(np.random.RandomState(1)
                         .randint(0, 2**32, V, dtype=np.uint64)
                         .astype(np.uint32))
    key = jnp.array([1, 2], jnp.uint32)
    kin = jnp.array([3, 4], jnp.uint32)

    ref_mask = jax.jit(lambda: mask_add_ref(x, key, 0))
    jax.block_until_ready(ref_mask())
    t_oracle = wall(lambda: jax.block_until_ready(ref_mask()))

    ref_chain = jax.jit(lambda: chain_combine_ref(cipher, x, kin, key, 0))
    jax.block_until_ready(ref_chain())
    t_chain = wall(lambda: jax.block_until_ready(ref_chain()))

    # HBM traffic per element (TPU):
    #   unfused mask_add: pad write+read (8) + x read (4) + out write (4) = 16 B
    #   fused kernel:     x read (4) + out write (4)                     =  8 B
    #   unfused chain hop: 2 pads (16) + cipher r/w (8) + x (4) + out (4)= 32 B
    #   fused chain hop:  cipher (4) + x (4) + out (4)                   = 12 B
    payload = {
        "elements": V,
        "mask_add": {"oracle_wall_s": t_oracle,
                     "bytes_per_elem_fused": 8,
                     "bytes_per_elem_unfused": 16,
                     "hbm_traffic_reduction": 2.0},
        "chain_combine": {"oracle_wall_s": t_chain,
                          "bytes_per_elem_fused": 12,
                          "bytes_per_elem_unfused": 32,
                          "hbm_traffic_reduction": 32 / 12},
        # Threefry-2x32: 20 rounds x ~6 uint32 VPU ops / 2 lanes
        "prf_vpu_ops_per_word": 60,
    }
    emit("kernel/mask_add", t_oracle * 1e6,
         f"fused 8B/elem vs 16B/elem unfused (2.0x HBM)")
    emit("kernel/chain_combine", t_chain * 1e6,
         f"fused 12B/elem vs 32B/elem unfused (2.7x HBM)")
    save_json("kernel_bench", payload)
    return payload


def main():
    run()


if __name__ == "__main__":
    main()
