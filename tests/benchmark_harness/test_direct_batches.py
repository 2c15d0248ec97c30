"""`bulk_direct_read_pct.rebuild` (PR 31) as the harness reads it: its
own data file over the program's own `/metrics` text, parsed by the
harness's parser, around one rebuild verb whose codec puts every batch
as the reader leg delivered it.  A program without the family (the
parent of PR 31) still counts its batches, which are the denominator:
the reader does not fail there and reads 0, the share of its batches
that such a program puts unstaged; a window without a rebuild batch
leaves the metric out.
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
from seaweedfs_tpu.stats import metrics as stats_metrics  # noqa: E402
from seaweedfs_tpu.storage import ec  # noqa: E402
from seaweedfs_tpu.storage.ec.layout import to_ext  # noqa: E402

NAME = "bulk_direct_read_pct.rebuild"
FAMILY = "ec_bulk_direct_batches_total"


def scrape() -> str:
    return generate_latest(stats_metrics.REGISTRY).decode()


def without_the_family(text: str) -> dict:
    return parse_metrics("\n".join(
        line for line in text.splitlines() if FAMILY not in line))


@pytest.fixture(scope="module")
def windows(tmp_path_factory):
    """The `/metrics` text around a rebuild of two shards of 12 KiB in
    three batches, under the XLA codec (a device put) and the host's."""
    base = str(tmp_path_factory.mktemp("direct") / "1")
    rng = np.random.default_rng(31)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, size=120_000, dtype=np.uint8).tobytes())
    ec.write_ec_files(base, backend="cpu", large_block=8192, small_block=1024)
    texts = {}
    for backend in ("xla", "cpu"):
        for lost in (3, 11):
            os.remove(base + to_ext(lost))
        before = scrape()
        assert ec.rebuild_ec_files(
            base, backend=backend, stride=4096) == [3, 11]
        texts[backend] = (before, scrape())
    return texts


def test_the_reader_takes_the_direct_share_of_the_windows_batches(windows):
    spec = metrics_eval.load_reader(NAME)["ratio"]
    before, after = (parse_metrics(text) for text in windows["xla"])
    assert metrics_eval.ratio(spec, before, after, {}) == 100.0
    before, after = (parse_metrics(text) for text in windows["cpu"])
    assert metrics_eval.ratio(spec, before, after, {}) == 0.0


def test_a_program_without_the_family_reads_zero_and_does_not_fail(windows):
    spec = metrics_eval.load_reader(NAME)["ratio"]
    before, after = (without_the_family(text) for text in windows["xla"])
    assert after  # the parent's text still has every other family
    assert metrics_eval.ratio(spec, before, after, {}) == 0.0
    # no rebuild batch in the window: nothing to read
    assert metrics_eval.ratio(spec, after, after, {}) is None


def test_the_family_is_exposed_for_every_pipeline_from_the_start():
    pipelines = {dict(labels)["pipeline"]
                 for (name, labels) in parse_metrics(scrape())
                 if name == PREFIX + FAMILY}
    assert pipelines == {"encode", "rebuild", "verify"}
