"""int8+EF cross-pod gradient compression: standalone lowering + quality check.

The full-train pod-compression lowering trips an XLA SPMD partitioner CHECK on
this build (EXPERIMENTS.md §Perf, refuted-hypothesis log), so the collective
evidence comes from a standalone grads-only module: the HLO must contain an
s8 all-reduce over the pod axis (1 byte/elem on the cross-pod wire vs 4 for
f32), and error feedback must keep the long-run compressed-gradient average
unbiased.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def run() -> Dict:
    import os

    # a tiny private mesh is enough to lower the collective pattern
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from repro.training.compression import compressed_psum_pod

    devs = jax.local_device_count()
    mesh = jax.make_mesh((1,), ("pod",))
    g = {"w": jnp.asarray(np.linspace(-1, 1, 4096).reshape(64, 64), jnp.float32)}
    ef = {"w": jnp.zeros((1, 64, 64), jnp.bfloat16)}

    def step(g_, ef_):
        f = shard_map(
            lambda gg, ee: compressed_psum_pod(gg, ee, axis="pod", pod_count=1),
            mesh=mesh, in_specs=(P(), P("pod")), out_specs=(P(), P("pod")),
            check_vma=False,
        )
        return f(g_, ef_)

    lowered = jax.jit(step).lower(g, ef)
    txt = lowered.as_text()
    has_int8_wire = ("s8" in txt or "i8" in txt) and "all_reduce" in txt.replace("-", "_")
    comp = lowered.compile()

    # unbiasedness under error feedback
    acc = jnp.zeros((64, 64))
    cur = ef
    n = 25
    for _ in range(n):
        out, cur = step(g, cur)
        acc = acc + out["w"]
    bias = float(jnp.abs(acc / n - g["w"]).max())
    return {
        "int8_on_wire_in_hlo": bool(has_int8_wire),
        "ef_bias_after_25_steps": bias,
        "wire_bytes_ratio_vs_f32": 0.25,
        "note": "full-train lowering hits XLA spmd_partitioner_util.cc:504 "
                "CHECK on this build; logged as refuted in §Perf",
    }


def main() -> None:
    r = run()
    for k, v in r.items():
        print(f"  {k}: {v}")
    assert r["ef_bias_after_25_steps"] < 5e-3
    print("compression,ef_bias,%s" % r["ef_bias_after_25_steps"])


if __name__ == "__main__":
    main()
