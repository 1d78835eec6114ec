"""The benchmark's work counts (perfbench/harness/work.py) against
hand-computed shapes, and its peaks table."""

import pytest

from perfbench.harness import device, work

V5E = {"bf16_flop_s": 197e12, "int8_op_s": 393e12, "hbm_bytes_s": 819e9,
       "hbm_bytes": 16e9}


def test_gf_encode_work_of_one_4m_object():
    # 4 MiB / (8 x 4 KiB) = 128 stripes; 8 data + 4 parity chunks each
    w = work.gf_encode_work(stripes=128, k=8, m=4, stripe_unit=4096)
    assert w["bytes"] == 4 * 2**20 + 2 * 2**20         # 4 MiB in, 2 out
    assert w["ops"] == 2 * 8 * 4 * 128 * 4096          # mul + add
    least, bound = work.least_seconds(w, V5E)
    assert bound == "bytes"
    assert least == pytest.approx(6 * 2**20 / 819e9)


def test_digest_work_counts_every_stored_block_once():
    # one 4 MiB object on 8+4: 12 shards x 128 blocks of 4 KiB
    w = work.digest_work(blocks=12 * 128, block_size=4096)
    assert w == {"ops": 0, "bytes": 1536 * 4100}


def test_crush_work_of_one_epoch_on_the_10k_map():
    w = work.crush_work(pgs=65536, numrep=3, bucket_sizes=(250, 40))
    assert w["draws"] == 65536 * 3 * 290
    assert w["ops"] == w["draws"] * work.STRAW2_OPS_PER_DRAW
    assert work.STRAW2_OPS_PER_DRAW == 198
    assert w["bytes"] == 65536 * 16
    least, bound = work.least_seconds(w, V5E)
    assert bound == "ops"
    assert least == pytest.approx(65536 * 3 * 290 * 198 / 393e12)


def test_roofline_share_is_silent_without_device_time_or_work():
    w = work.digest_work(1536, 4096)
    assert work.roofline_share(w, V5E, 0.0) is None
    assert work.roofline_share(work.digest_work(0, 4096), V5E, 1.0) is None
    least = 1536 * 4100 / 819e9
    assert work.roofline_share(w, V5E, 4 * least) == pytest.approx(25.0)


def test_peaks_table_knows_the_v5e_and_nothing_unnamed():
    table = device.load_peaks()
    assert "Google Cloud" in table["source"]
    assert device.peaks_of("TPU v5 lite") == V5E
    for kind in ("cpu", "TPU v9", "source"):
        with pytest.raises(KeyError):
            device.peaks_of(kind)
