"""An RBD image with a separate data pool (`rbd create --data-pool`):
header, directory, object map and the striper's size object stay in the
image's replicated pool, the rbd_data objects go to an erasure-coded
pool with allow_ec_overwrites, and `aio_write` (librbd rbd_aio_write)
acknowledges what `write` does.  Every read is held to a plain model of
the image."""

from __future__ import annotations

import numpy as np
import pytest

from ceph_tpu.rbd import FEATURE_OBJECT_MAP, Image, ImageCompletion
from ceph_tpu.tools.vstart import MiniCluster

K, M = 4, 2
OBJ = 1 << 16


@pytest.fixture(scope="module")
def pools(tmp_path_factory):
    c = MiniCluster(n_osds=K + M, ms_type="loopback", store_type="bluestore",
                    base_path=str(tmp_path_factory.mktemp("rbdec"))).start()
    c.wait_for_osd_count(K + M)
    client = c.client()
    meta = c.create_pool(client, pg_num=4)
    ec = c.create_pool(client, pg_num=4, pool_type="erasure", k=K, m=M,
                       ec_overwrites=True)
    yield c, client.open_ioctx(meta), client.open_ioctx(ec)
    c.stop()


def _bytes(*key, n: int) -> bytes:
    return np.random.default_rng((0x4bd, *key)).bytes(n)


def _image(meta, ec, name: str, size: int = 8 * OBJ, **kw) -> Image:
    kw.setdefault("stripe_unit", OBJ)
    kw.setdefault("stripe_count", 1)
    return Image.create(meta, name, size=size, order=16, data_pool=ec, **kw)


def test_metadata_stays_beside_the_header_and_data_goes_to_the_ec_pool(
        pools):
    _c, meta, ec = pools
    img = _image(meta, ec, "layout", features=[FEATURE_OBJECT_MAP])
    img.write(_bytes(1, n=3 * OBJ), OBJ // 2)
    in_meta, in_ec = set(meta.list_objects()), set(ec.list_objects())
    assert {"rbd_header.layout", "rbd_directory",
            "rbd_data.layout.meta"} <= in_meta
    assert not any(o.startswith("rbd_data.layout.0") for o in in_meta)
    data = {o for o in in_ec if o.startswith("rbd_data.layout.")}
    assert data == {f"rbd_data.layout.{n:016x}" for n in range(4)}
    assert not any(o.startswith("rbd_header") for o in in_ec)
    assert Image(meta, "layout").stat()["size"] == 8 * OBJ
    # the object map, rebuilt from the data pool, sees the same objects
    assert img.rebuild_object_map() == 4


def test_an_ec_data_pool_without_overwrites_is_refused(pools):
    c, meta, _ec = pools
    client = meta.client
    plain_ec = client.open_ioctx(c.create_pool(
        client, pg_num=1, pool_type="erasure", k=K, m=M))
    with pytest.raises(OSError) as err:
        Image.create(meta, "refused", size=OBJ, data_pool=plain_ec)
    assert err.value.errno == 95
    assert "rbd_header.refused" not in meta.list_objects()


def test_aio_write_acknowledges_what_write_does(pools):
    _c, meta, ec = pools
    a = _image(meta, ec, "sync")
    b = _image(meta, ec, "aio")
    model = bytearray(8 * OBJ)
    comps = []
    for i in range(12):
        rng = np.random.default_rng((0x4bd, 2, i))
        off = int(rng.integers(8 * OBJ // 4096)) * 4096
        data = rng.bytes(4096)
        model[off:off + 4096] = data
        a.write(data, off)
        comps.append(b.aio_write(data, off))
        # one in flight at a time: a later write may cover this one
        assert comps[-1].wait_for_complete(30)
    assert all(c.get_return_value() == 0 for c in comps)
    assert a.read(0, 8 * OBJ) == b.read(0, 8 * OBJ) == bytes(model)


@pytest.mark.parametrize("striping", [(OBJ, 1), (OBJ // 4, 4)],
                         ids=["object_units", "four_wide"])
def test_writes_and_reads_across_objects_are_exact(pools, striping):
    _c, meta, ec = pools
    su, sc = striping
    img = _image(meta, ec, f"cross-{sc}", stripe_unit=su, stripe_count=sc)
    model = bytearray(8 * OBJ)
    for i, (off, n) in enumerate([(OBJ - 100, 300), (3 * OBJ - 5000,
                                                     2 * OBJ + 9000),
                                  (0, 5 * OBJ // 2), (7 * OBJ + 1, 999)]):
        data = _bytes(3, sc, i, n=n)
        model[off:off + n] = data
        if i % 2:
            img.write(data, off)
        else:
            c = img.aio_write(data, off)
            assert isinstance(c, ImageCompletion)
            assert c.wait_for_complete(30) and c.get_return_value() == 0
    assert img.read(0, 8 * OBJ) == bytes(model)
    assert img.read(OBJ - 150, 3 * OBJ) == bytes(model[OBJ - 150:4 * OBJ
                                                       - 150])


def test_snapshots_of_an_image_with_a_data_pool_are_refused(pools):
    _c, meta, ec = pools
    img = _image(meta, ec, "nosnap")
    with pytest.raises(OSError) as err:
        img.snap_create("s")
    assert err.value.errno == 95
