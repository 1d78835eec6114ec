"""Sharded multi-chip execution of the EC + CRUSH data path.

The reference's distributed write (SURVEY.md §3.3) is: place the PG with CRUSH,
encode the stripe into k+m shards, fan the shards out to OSDs over the cluster
messenger, and on recovery fan k shards back in.  On a TPU mesh the same step is:

    place   flat straw2 firstn, batched over PGs     [dp x ec sharded, elementwise]
    encode  batched GF(2^8) matmul on the MXU        [stripes sharded]
    scatter shard axis resharded over the ec axis    [XLA all_to_all on ICI]
    recover all_gather shards along ec + decode      [explicit shard_map collective]
    stats   device utilization histogram             [psum over the whole mesh]

Everything is one jitted function over a ("dp", "ec") Mesh; XLA inserts the
collectives from the sharding annotations, exactly the scaling-book recipe.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ceph_tpu.gf.matrix import recovery_matrix
from ceph_tpu.gf.tables import bit_matrix
from ceph_tpu.ops.gf_kernel import _encode_xla as _encode_impl
from ceph_tpu.ops.crush_kernel import flat_firstn


def sharded_encode(mesh, coeff: np.ndarray, data, dot_dtype=jnp.bfloat16):
    """Encode with stripes sharded across every device in the mesh.

    data: (S, k, B) uint8, S divisible by mesh size.  Pure data parallelism —
    the TPU analog of ECUtil's per-stripe loop (src/osd/ECUtil.cc:136) run on
    all chips at once.
    """
    coeff = np.asarray(coeff, dtype=np.uint8)
    m, k = coeff.shape
    w = jnp.asarray(bit_matrix(coeff))
    spec = NamedSharding(mesh, P(("dp", "ec"), None, None))
    data = jax.device_put(jnp.asarray(data, dtype=jnp.uint8), spec)
    fn = jax.jit(
        functools.partial(_encode_impl, k=k, m=m, dot_dtype=dot_dtype),
        out_shardings=spec,
    )
    return fn(w, data)


def make_cluster_step(mesh, gen: np.ndarray, ids, weights, reweight,
                      *, numrep: int, erasures: tuple[int, ...],
                      dot_dtype=jnp.bfloat16):
    """Build the flagship distributed step: place + encode + scatter + recover.

    gen      : (k+m, k) uint8 systematic generator matrix (identity on top).
    ids      : (S,) device ids of the flat straw2 root     (placement operand)
    weights  : (S,) 16.16 straw2 weights
    reweight : (D,) 16.16 reweight vector
    numrep   : replicas to place per PG
    erasures : static chunk indices simulated lost; recovery rebuilds them from
               the first k surviving chunks via an all_gather over the ec axis
               (the MOSDECSubOpRead fan-in, ECBackend.cc:2301 analog).

    Returns step(xs, data) -> dict with placements, parity, recovered chunks,
    utilization histogram, and mismatches (recovered-vs-original check, 0 when
    the math is right).  xs: (N,) uint32; data: (S, k, B) uint8.
    """
    gen = np.asarray(gen, dtype=np.uint8)
    k = gen.shape[1]
    m = gen.shape[0] - k
    n_chunks = k + m
    ec_size = mesh.shape["ec"]
    if n_chunks % ec_size:
        raise ValueError(f"k+m={n_chunks} not divisible by ec axis {ec_size}")
    coding = gen[k:]
    w_enc = jnp.asarray(bit_matrix(coding))
    chosen = [i for i in range(n_chunks) if i not in set(erasures)][:k]
    rmat = recovery_matrix(gen, chosen, list(erasures))
    w_rec = jnp.asarray(bit_matrix(rmat))
    n_lost = len(erasures)
    chosen_arr = jnp.asarray(chosen, dtype=jnp.int32)
    lost_arr = jnp.asarray(list(erasures), dtype=jnp.int32)

    ids = jnp.asarray(ids, dtype=jnp.int32)
    weights = jnp.asarray(weights, dtype=jnp.int64)
    reweight = jnp.asarray(reweight, dtype=jnp.int64)
    max_dev = int(reweight.shape[0])

    batch_spec = NamedSharding(mesh, P(("dp", "ec")))
    stripe_spec = NamedSharding(mesh, P(("dp", "ec"), None, None))
    shard_spec = NamedSharding(mesh, P("dp", "ec", None))  # chunk axis over ec
    repl = NamedSharding(mesh, P())

    def recover(chunks):
        """chunks block: (S/dp, n_chunks/ec, B) — gather shards, rebuild lost."""
        full = jax.lax.all_gather(chunks, "ec", axis=1, tiled=True)
        surv = jnp.take(full, chosen_arr, axis=1)
        rebuilt = _encode_impl(w_rec, surv, k=k, m=n_lost, dot_dtype=dot_dtype)
        truth = jnp.take(full, lost_arr, axis=1)
        local_bad = jnp.sum(rebuilt != truth)
        # every ec shard computes the same comparison post-gather; count it once
        local_bad = jnp.where(jax.lax.axis_index("ec") == 0, local_bad, 0)
        bad = jax.lax.psum(local_bad, ("dp", "ec"))
        return rebuilt, bad

    recover_sharded = jax.shard_map(
        recover, mesh=mesh,
        in_specs=(P("dp", "ec", None),),
        out_specs=(P("dp", None, None), P()),
        check_vma=False,
    )

    def step(xs, data):
        placements = flat_firstn(xs, ids, weights, reweight,
                                 numrep=numrep, tries=51)
        parity = _encode_impl(w_enc, data, k=k, m=m, dot_dtype=dot_dtype)
        chunks = jnp.concatenate([data, parity], axis=1)  # (S, k+m, B)
        # reshard: stripes over dp, chunk fan-out over ec (the shard scatter)
        chunks = jax.lax.with_sharding_constraint(chunks, shard_spec)
        rebuilt, mismatches = recover_sharded(chunks)
        valid = placements != 0x7FFFFFFF
        util = jnp.sum(
            jax.nn.one_hot(jnp.where(valid, placements, 0), max_dev,
                           dtype=jnp.int32) * valid[..., None].astype(jnp.int32),
            axis=(0, 1),
        )
        return {
            "placements": placements,
            "parity": parity,
            "rebuilt": rebuilt,
            "utilization": util,
            "mismatches": mismatches,
        }

    return jax.jit(
        step,
        in_shardings=(batch_spec, stripe_spec),
        out_shardings={
            "placements": batch_spec,
            "parity": stripe_spec,
            "rebuilt": NamedSharding(mesh, P("dp", None, None)),
            "utilization": repl,
            "mismatches": repl,
        },
    )
