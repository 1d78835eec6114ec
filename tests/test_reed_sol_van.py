"""jerasure's `reed_sol_van` (w = 8) as the program builds it, against
the plain construction the benchmark holds
(perfbench/reference/rs_plain.py, which imports nothing of the program).

The program's own oracles (`ec_encode_ref`, `recovery_matrix`) share the
program's coding matrix and cannot witness it: every comparison here is
with `rs_plain`, through the paths a pool takes — the codec's encode over
the OSD's stripe layout, and the dispatch engine's batched decode with
its pattern table.
"""

import itertools
import os

import numpy as np
import pytest

from ceph_tpu.ec import registry_instance
from ceph_tpu.ec.jerasure import big_vandermonde_distribution_matrix
from ceph_tpu.ops import telemetry
from ceph_tpu.ops.dispatch import DeviceDispatchEngine
from ceph_tpu.osd.daemon import OSDDaemon
from ceph_tpu.osd.ec_util import StripeInfo
from ceph_tpu.tools.ec_non_regression import DEFAULT_DIR
from perfbench.reference import rs_plain

K, M, SU = 8, 4, 4096
WIDTH = K * SU

#: the coding rows of the jerasure manual's reed_sol_01 example (k = 7,
#: m = 7, w = 8)
MANUAL_7_7 = [
    [1, 1, 1, 1, 1, 1, 1],
    [1, 199, 210, 240, 105, 121, 248],
    [1, 70, 91, 245, 56, 142, 167],
    [1, 170, 114, 42, 87, 78, 231],
    [1, 38, 236, 53, 233, 175, 65],
    [1, 64, 174, 232, 52, 237, 39],
    [1, 187, 104, 210, 211, 105, 186],
]

#: 2 <= k <= 16, 1 <= m <= 4: the upstream docs' profiles — (4, 2),
#: (8, 3), (8, 4), (10, 4) — are among them
SWEEP = [(k, m) for k in range(2, 17) for m in range(1, 5)]


@pytest.mark.parametrize("k,m", SWEEP + [(7, 7)],
                         ids=lambda v: str(v))
def test_distribution_matrix_is_jerasures(k, m):
    """Identity on top, then `rs_plain.coding_matrix`'s rows, the first
    of them all ones (reed_sol.c's column scaling); no pivot was zero,
    or `gf_inv` would have raised."""
    dist = np.asarray(big_vandermonde_distribution_matrix(k + m, k))
    assert dist.dtype == np.uint8 and dist.shape == (k + m, k)
    assert dist[:k].tolist() == np.eye(k, dtype=np.uint8).tolist()
    assert (dist[k] == 1).all()
    assert dist[k:].tolist() == rs_plain.coding_matrix(k, m).tolist()
    if (k, m) == (7, 7):
        assert dist[k:].tolist() == MANUAL_7_7


def _codec(runtime: str = "tpu"):
    return registry_instance().factory(
        "jerasure", {"technique": "reed_sol_van", "k": str(K), "m": str(M),
                     "runtime": runtime})


def _payload(seed: int, size: int) -> bytes:
    return np.random.default_rng((0x8454, seed)).bytes(size)


#: whole stripes and not: one byte, under a chunk, a chunk and a bit,
#: one stripe, two stripes less one byte, 2.6 stripes, four stripes
SIZES = [1, 4095, SU + 17, WIDTH, 2 * WIDTH - 1, 85_197, 4 * WIDTH]


@pytest.mark.parametrize("runtime", ["cpu", "tpu"])
@pytest.mark.parametrize("size", SIZES)
def test_encode_equals_the_plain_reference_shard_for_shard(size, runtime):
    """The OSD's whole-object encode (`_ec_encode_window`: the pool's
    stripe layout around `encode_chunks`) at k = 8, m = 4, stripe_unit
    4096, on the host oracle and on the kernel route."""
    codec, si = _codec(runtime), StripeInfo(K, SU)
    payload = _payload(size, size)
    got = OSDDaemon._ec_encode_window(
        codec, si, payload, 0, si.object_stripes(len(payload)))
    want = rs_plain.shards_of(payload, K, M, SU)
    assert sorted(got) == list(range(K + M))
    for s in range(K + M):
        assert got[s] == want[s], f"shard {s}"


#: every way to lose one or two of the twelve shards: 12 + 66
ERASURES = [e for n in (1, 2)
            for e in itertools.combinations(range(K + M), n)]
assert len(ERASURES) == 78


@pytest.fixture(scope="module")
def decode_engine():
    eng = DeviceDispatchEngine(max_delay_us=200.0,
                               stats=telemetry.DecodeDispatchStats())
    yield eng
    eng.stop()


@pytest.fixture(scope="module")
def kernel_codec():
    return _codec()


@pytest.fixture(scope="module")
def reference_shards():
    """(S, k + m, su) chunks of a 2.6-stripe object, from the plain
    reference alone."""
    shards = rs_plain.shards_of(_payload(78, 85_197), K, M, SU)
    return np.stack([np.frombuffer(s, dtype=np.uint8).reshape(-1, SU)
                     for s in shards], axis=1)


@pytest.mark.parametrize("erased", ERASURES,
                         ids=lambda e: "-".join(map(str, e)))
def test_batched_decode_rebuilds_the_reference_shards(
        erased, kernel_codec, decode_engine, reference_shards):
    """Through `submit_decode_chunks` — the pattern table and the
    batched decode kernel a degraded read takes — the lost shards of an
    object that the *reference* encoded come back bit-exact."""
    chosen = [s for s in range(K + M) if s not in erased][:K]
    got = kernel_codec.submit_decode_chunks(
        decode_engine, chosen, reference_shards[:, chosen],
        list(erased)).result(timeout=120)
    assert np.array_equal(np.asarray(got), reference_shards[:, erased])


@pytest.mark.parametrize("name,k,m", [("jerasure_rsvan_k4m2", 4, 2),
                                      ("jerasure_rsvan_k7m3", 7, 3),
                                      ("jerasure_rsvan_k8m3", 8, 3),
                                      ("jerasure_rsvan_k8m4", 8, 4)])
def test_golden_reed_sol_van_archives_hold_jerasures_parity(name, k, m):
    """The corpus pins what the program encodes; this pins that what it
    pinned is the profile's code."""
    stored = np.load(os.path.join(DEFAULT_DIR, name + ".npz"))
    chunks = [stored[f"chunk_{i}"].tobytes() for i in range(k + m)]
    # one stripe whose unit is the archive's chunk: the codec's layout
    assert chunks == rs_plain.shards_of(b"".join(chunks[:k]), k, m,
                                        len(chunks[0])), name
