"""Clay on the engines: the device route (``ops/clay_kernel.py``, jax on
the CPU here) against the codec's host layered code (``runtime`` cpu)
and the plain reference (perfbench/reference/clay_plain.py, which
imports nothing of the program), byte for byte.

4+2 with every erasure pattern of one and two nodes; 8+4 (alpha = 64)
with a seeded sample of one to four erasures — data only, parity only,
mixed, two in one grid row; a batch that mixes patterns through one
decode engine; the encode through the encode engine; the profile's d;
and a flagged pool's stripe-ranged overwrite (the two-OSDs-down read is
in test_path_selection.py).
"""

from __future__ import annotations

import itertools

import numpy as np
import pytest

from ceph_tpu.ec import registry_instance
from ceph_tpu.ops import clay_kernel, telemetry
from ceph_tpu.ops.dispatch import DeviceDispatchEngine
from perfbench.reference import clay_plain


def _codec(k: int, m: int, runtime: str = "tpu"):
    return registry_instance().factory(
        "clay", {"k": str(k), "m": str(m), "runtime": runtime})


def _stripes(s: int, k: int, su: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, 256, (s, k, su), dtype=np.uint8)


def _plain_all(data: np.ndarray, k: int, m: int) -> np.ndarray:
    """(S, k + m, su): the plain reference's chunks of every node."""
    every = clay_plain.decode_stripes(
        {i: data[:, i] for i in range(k)}, k, m)
    return np.stack([every[i] for i in range(k + m)], axis=1)


def _erasures_8_4() -> list[tuple[int, ...]]:
    rng = np.random.default_rng(84)
    picked = [(3,), (9,), (2, 5), (8, 11), (1, 10), (4, 5), (8, 9),
              (0, 1, 2, 3), (8, 9, 10, 11), (2, 5, 10, 11), (0, 4, 8),
              (6, 7)]
    for n in (1, 2, 3, 4):
        combos = list(itertools.combinations(range(12), n))
        picked += [combos[i] for i in rng.choice(len(combos), 3,
                                                 replace=False)]
    return sorted(set(picked))


CASES = ([(4, 2, 64 * 8, e) for n in (1, 2)
          for e in itertools.combinations(range(6), n)]
         + [(8, 4, 4096, e) for e in _erasures_8_4()])


@pytest.fixture(scope="module")
def corpus():
    """Per geometry: seeded stripes and every node's chunks by the plain
    reference."""
    out = {}
    for k, m, su in ((4, 2, 64 * 8), (8, 4, 4096)):
        data = _stripes(3, k, su, seed=k)
        out[k] = (data, _plain_all(data, k, m))
    return out


def test_encode_equals_host_code_and_plain_reference(corpus):
    for k, m in ((4, 2), (8, 4)):
        data, want = corpus[k]
        dev = np.asarray(_codec(k, m).encode_chunks(data))
        host = _codec(k, m, "cpu").encode_chunks(data)
        assert (dev == want[:, k:]).all() and (host == want[:, k:]).all()


@pytest.mark.parametrize("k,m,su,erased", CASES,
                         ids=lambda v: "-".join(map(str, v))
                         if isinstance(v, tuple) else str(v))
def test_decode_equals_host_code_and_plain_reference(corpus, k, m, su,
                                                     erased):
    _data, full = corpus[k]
    chosen = [i for i in range(k + m) if i not in erased][:k]
    targets = [i for i in range(k + m) if i not in chosen]
    dev = np.asarray(_codec(k, m).decode_chunks(
        chosen, full[:, chosen], targets))
    host = _codec(k, m, "cpu").decode_chunks(
        chosen, full[:, chosen], targets)
    assert (dev == full[:, targets]).all()
    assert (host == full[:, targets]).all()


def test_one_stripe_is_the_whole_object_encode():
    """The per-stripe layout does not change the codec's bytes: a stripe
    is what encode() of its k * su bytes gives."""
    codec = _codec(8, 4, "cpu")
    data = _stripes(2, 8, 4096, seed=5)
    parity = codec.encode_chunks(data)
    for s in range(2):
        whole = codec.encode(set(range(12)), data[s].tobytes())
        assert [whole[8 + j] for j in range(4)] == [
            parity[s, j].tobytes() for j in range(4)]


def test_mixed_patterns_share_one_decode_call():
    """Reads with different erasure patterns coalesce into one call of
    the Clay program; each gets its own rebuilt chunks."""
    codec = _codec(8, 4)
    stats = telemetry.DecodeDispatchStats()
    eng = DeviceDispatchEngine(max_delay_us=200_000.0, stats=stats)
    lost = [(2, 5), (0, 7), (4, 5), (1, 3)]
    reads = []
    for i, er in enumerate(lost):
        full = _plain_all(_stripes(4 + i, 8, 4096, seed=20 + i), 8, 4)
        chosen = [j for j in range(12) if j not in er][:8]
        codec._dense(tuple(chosen), er)     # built before the reads race
        reads.append((chosen, full[:, chosen], er, full[:, list(er)]))
    try:
        futs, wants = [], []
        for chosen, data, er, want in reads:
            futs.append(codec.submit_decode_chunks(eng, chosen, data, er))
            wants.append(want)
        for f, want in zip(futs, wants):
            assert (np.asarray(f.result(timeout=300)) == want).all()
        kernels = stats.phases.summary()["kernels"]
        assert kernels["ec_decode_clay"]["batches"] < len(lost)
    finally:
        eng.stop()


def test_one_batch_of_interleaved_patterns_one_call_a_group():
    """A batch whose stripes interleave four patterns: each group is
    one call of the program, and every stripe comes back in its place."""
    codec = _codec(8, 4)
    lost = [(2, 5), (1, 6), (0, 4), (3, 7)]
    datas, wants, pidx = [], [], []
    for i, er in enumerate(lost):
        full = _plain_all(_stripes(3, 8, 4096, seed=40 + i), 8, 4)
        chosen = [j for j in range(12) if j not in er][:8]
        datas.append(full[:, chosen])
        wants.append(full[:, list(er)])
        pidx += [i] * 3
    order = np.random.default_rng(1).permutation(12)
    data = np.concatenate(datas)[order]
    want = np.concatenate(wants)[order]
    pidx = np.asarray(pidx)[order]
    groups = []
    for i, er in enumerate(lost):
        chosen = [j for j in range(12) if j not in er][:8]
        _idx, _tab, w = codec._dense(tuple(chosen), er)
        groups.append((w, np.nonzero(pidx == i)[0]))
    before = clay_kernel.clay_decode._cache_size()
    got = np.asarray(clay_kernel.run("decode", groups, data, alpha=64))
    assert (got == want).all()
    # four groups of three stripes share one bucket: one program
    assert clay_kernel.clay_decode._cache_size() - before <= 1


def test_encode_through_the_engine_and_its_host_stand_in():
    codec = _codec(4, 2)
    data = _stripes(5, 4, 512, seed=9)
    want = _plain_all(data, 4, 2)[:, 4:]
    eng = DeviceDispatchEngine(stats=telemetry.DispatchStats())
    try:
        got = codec.submit_chunks(eng, data).result(timeout=300)
        assert (np.asarray(got) == want).all()
    finally:
        eng.stop()
    assert (codec._host_layered((0, 1, 2, 3), data) == want).all()


@pytest.mark.parametrize("d,ok", [(None, True), ("11", True),
                                  ("10", False)])
def test_profile_d(d, ok):
    profile = {"k": "8", "m": "4"}
    if d is not None:
        profile["d"] = d
    if not ok:
        with pytest.raises(ValueError):
            registry_instance().factory("clay", profile)
        return
    codec = registry_instance().factory("clay", profile)
    assert (codec.d, codec.get_sub_chunk_count()) == (11, 64)
    # the stripe unit's quantum is a whole number of sub-chunks
    assert codec.get_alignment() // codec.k == 64


def test_flagged_pool_overwrites_a_stripe_range(tmp_path):
    """allow_ec_overwrites on a Clay pool: a partial write re-encodes the
    stripes it touches (stripe info follows from the layout), and an
    unflagged pool still refuses it."""
    from ceph_tpu.tools.vstart import MiniCluster
    c = MiniCluster(n_osds=6, ms_type="loopback", store_type="bluestore",
                    base_path=str(tmp_path)).start()
    try:
        c.wait_for_osd_count(6)
        client = c.client(timeout=60.0)
        flagged = c.create_pool(client, pg_num=2, pool_type="erasure",
                                plugin="clay", k=4, m=2,
                                ec_overwrites=True)
        io = client.open_ioctx(flagged)
        base = bytearray(np.random.default_rng(3).bytes(3 * 4 * 4096))
        io.write_full("o", bytes(base))
        io.write("o", b"Z" * 5000, offset=6000)
        base[6000:11000] = b"Z" * 5000
        assert io.read("o") == bytes(base)
        before = sum(d.perf.value("ec_rmw_writes") for d in c.osds.values())
        assert before >= 1
        plain = c.create_pool(client, pg_num=2, pool_type="erasure",
                              plugin="clay", k=4, m=2)
        io2 = client.open_ioctx(plain)
        io2.write_full("o", bytes(base))
        with pytest.raises(OSError):
            io2.write("o", b"Y" * 100, offset=5)
        assert io2.read("o") == bytes(base)
    finally:
        c.stop()
