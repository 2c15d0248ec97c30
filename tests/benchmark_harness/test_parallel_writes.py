"""`bulk_parallel_write_pct.encode` as the harness reads it: its
own data file over the program's own `/metrics` text, parsed by the
harness's parser, around encode verbs of four batches.  Overlapped, the
writer leg writes every batch's fourteen rows side by side and each
batch counts; the serial mode writes inline and counts none.  An older
program without the family still counts its batches, which are the
denominator: the reader does not fail there and reads 0; a window
without an encode batch leaves the metric out.
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

NAME = "bulk_parallel_write_pct.encode"
FAMILY = "ec_bulk_parallel_write_batches_total"


def scrape() -> str:
    return generate_latest(stats_metrics.REGISTRY).decode()


def without_the_family(text: str) -> dict:
    return parse_metrics("\n".join(
        line for line in text.splitlines() if FAMILY not in line))


@pytest.fixture(scope="module")
def windows(tmp_path_factory):
    """The `/metrics` text around an encode of 120,000 random bytes in
    four batches (one 8 KiB-block row, 4 KiB a batch, then a 1 KiB-block
    row, and the short last one), overlapped and serial."""
    base = str(tmp_path_factory.mktemp("parallel") / "1")
    rng = np.random.default_rng(39)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(1, 256, size=120_000, dtype=np.uint8).tobytes())
    texts = {}
    for overlap in (True, False):
        before = scrape()
        ec.write_ec_files(base, backend="cpu", stride=4096, large_block=8192,
                          small_block=1024, overlap=overlap)
        texts[overlap] = (before, scrape())
    return texts


def reader():
    return metrics_eval.load_reader(NAME)["ratio"]


def test_the_reader_takes_the_share_of_batches_written_side_by_side(
        windows):
    before, after = (parse_metrics(text) for text in windows[True])
    assert metrics_eval.ratio(reader(), before, after, {}) == 100.0
    before, after = (parse_metrics(text) for text in windows[False])
    assert metrics_eval.ratio(reader(), before, after, {}) == 0.0


def test_a_program_without_the_family_reads_zero_and_does_not_fail(windows):
    before, after = (without_the_family(text) for text in windows[True])
    assert after  # the parent's text still has every other family
    assert metrics_eval.ratio(reader(), before, after, {}) == 0.0


def test_a_window_without_an_encode_batch_leaves_the_metric_out(windows):
    _, after = (parse_metrics(text) for text in windows[True])
    assert metrics_eval.ratio(reader(), after, after, {}) is None


def test_the_family_is_exposed_for_every_pipeline_from_the_start():
    pipelines = {dict(labels)["pipeline"]
                 for (name, labels) in parse_metrics(scrape())
                 if name == PREFIX + FAMILY}
    assert pipelines == {"encode", "rebuild", "verify"}
