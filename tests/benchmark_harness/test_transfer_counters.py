"""`ec_device_transfers_total` (PR 27) as the harness reads it:
`benchmark/layer_metrics/mesh_shards_skipped_pct.json` (PERF.md section
7, ask 7, landed by PR 35), over the program's own `/metrics` text,
parsed by the harness's parser.  A program without the family (the
parent of PR 27) gives the reader nothing to read, and the metric is
left out, not failed.
"""
import os
import sys

import numpy as np
import pytest
from prometheus_client import generate_latest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import metrics_eval  # noqa: E402
from benchmark.cluster import PREFIX, parse_metrics  # noqa: E402
from seaweedfs_tpu.ops import rs, rs_resident  # noqa: E402
from seaweedfs_tpu.stats import metrics as stats_metrics  # noqa: E402

FAMILY = "ec_device_transfers_total"
# the ratio ask 7 specified: skipped result shards over all of them
ASKED = {
    "num": [{"series": FAMILY, "labels": {"kind": "d2h_shard_skipped"}}],
    "den": [{"series": FAMILY, "labels": {"kind": "d2h_shard_skipped"}},
            {"series": FAMILY, "labels": {"kind": "d2h_shard_fetched"}}],
    "scale": 100,
}


def skipped_pct() -> dict:
    return metrics_eval.load_reader("mesh_shards_skipped_pct")["ratio"]


def scrape() -> str:
    return generate_latest(stats_metrics.REGISTRY).decode()


@pytest.fixture(scope="module")
def window():
    """The `/metrics` text before and after one lane-sharded batch on
    four CPU devices that leaves device 3 without rows."""
    rng = np.random.default_rng(27)
    data = rng.integers(0, 256, size=(10, 1 << 20), dtype=np.uint8)
    shards = rs.RSCodec(backend="numpy").encode_all(data)
    cache = rs_resident.DeviceShardCache(
        shard_quantum=1 << 18, mesh_devices=4, mesh_min_shard_bytes=0)
    cache.warm_sizes = ()
    for sid in range(14):
        if sid != 3:
            cache.put(27, sid, shards[sid])
    reqs = [(3, d * cache.stripe + 11, 3000) for d in range(3)]
    before = scrape()
    got = rs_resident.reconstruct_intervals(cache, 27, reqs)
    after = scrape()
    assert got == [shards[s][o:o + n].tobytes() for s, o, n in reqs]
    cache.clear()
    return before, after


def test_the_data_file_holds_the_asked_ratio():
    assert skipped_pct() == ASKED


def test_the_reader_takes_the_skipped_share_of_the_window(window):
    before, after = (parse_metrics(text) for text in window)
    # one call, four shards, one of them without an asked-for row
    assert metrics_eval.ratio(skipped_pct(), before, after, {}) == 25.0


def test_a_program_without_the_family_leaves_the_metric_out(window):
    before, after = (
        parse_metrics("\n".join(
            line for line in text.splitlines() if FAMILY not in line))
        for text in window)
    assert after  # the parent's text still has every other family
    assert metrics_eval.ratio(skipped_pct(), before, after, {}) is None


def test_the_family_is_exposed_with_all_four_kinds_from_the_start():
    kinds = {dict(labels)["kind"]
             for (name, labels) in parse_metrics(scrape())
             if name == PREFIX + FAMILY}
    assert kinds == {"h2d_async", "h2d_waited", "d2h_shard_fetched",
                     "d2h_shard_skipped"}
