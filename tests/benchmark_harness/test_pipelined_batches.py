"""`bulk_pipelined_batches_pct.rebuild` / `.encode` (PR 34) as the
harness reads them: their own data files over the program's own
`/metrics` text, parsed by the harness's parser, around one rebuild verb
of three batches.  Under a device codec the worker enqueues a submitted
successor before it fetches the oldest batch, so at least one and at
most all but the last of the verb's batches count; the host codec puts
nothing on a device and counts none.  A program without the family (the
parent of PR 34) still counts its batches, which are the denominator:
the reader does not fail there and reads 0; a window without a batch of
the pipeline leaves the metric out.
"""
import os
import sys
import time

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

NAMES = {pipeline: f"bulk_pipelined_batches_pct.{pipeline}"
         for pipeline in ("rebuild", "encode")}
FAMILY = "ec_bulk_pipelined_batches_total"
BATCHES = 3


def scrape() -> str:
    return generate_latest(stats_metrics.REGISTRY).decode()


def without_the_family(text: str) -> dict:
    return parse_metrics("\n".join(
        line for line in text.splitlines() if FAMILY not in line))


@pytest.fixture(scope="module")
def windows(tmp_path_factory):
    """The `/metrics` text around a rebuild of two shards of 12 KiB in
    three batches, under the XLA codec (a device put) and the host's.
    A put takes 20 ms here, as a 40 MiB one takes milliseconds on the
    chip's host: the reader leg's next batch is submitted inside it."""
    import jax

    base = str(tmp_path_factory.mktemp("pipelined") / "1")
    rng = np.random.default_rng(34)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, size=120_000, dtype=np.uint8).tobytes())
    ec.write_ec_files(base, backend="cpu", large_block=8192, small_block=1024)
    real_put = jax.device_put

    def slow_put(x, *args, **kw):
        time.sleep(0.02)
        return real_put(x, *args, **kw)

    texts = {}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "device_put", slow_put)
        for backend in ("xla", "cpu"):
            for lost in (3, 11):
                os.remove(base + to_ext(lost))
            before = scrape()
            assert ec.rebuild_ec_files(
                base, backend=backend, stride=4096) == [3, 11]
            texts[backend] = (before, scrape())
    return texts


def test_the_reader_takes_the_pipelined_share_of_the_windows_batches(windows):
    spec = metrics_eval.load_reader(NAMES["rebuild"])["ratio"]
    before, after = (parse_metrics(text) for text in windows["xla"])
    share = metrics_eval.ratio(spec, before, after, {})
    assert 100.0 / BATCHES <= share <= 100.0 * (BATCHES - 1) / BATCHES
    assert round(share * BATCHES / 100.0, 6) in (1.0, 2.0)
    before, after = (parse_metrics(text) for text in windows["cpu"])
    assert metrics_eval.ratio(spec, before, after, {}) == 0.0


def test_a_program_without_the_family_reads_zero_and_does_not_fail(windows):
    spec = metrics_eval.load_reader(NAMES["rebuild"])["ratio"]
    before, after = (without_the_family(text) for text in windows["xla"])
    assert after  # the parent's text still has every other family
    assert metrics_eval.ratio(spec, before, after, {}) == 0.0


def test_a_window_without_a_batch_of_the_pipeline_leaves_the_metric_out(
        windows):
    before, after = (parse_metrics(text) for text in windows["xla"])
    # the window ran no encode batch, and a window of no length none at all
    spec = metrics_eval.load_reader(NAMES["encode"])["ratio"]
    assert metrics_eval.ratio(spec, before, after, {}) is None
    spec = metrics_eval.load_reader(NAMES["rebuild"])["ratio"]
    assert metrics_eval.ratio(spec, after, after, {}) is None


def test_the_family_is_exposed_for_every_pipeline_from_the_start():
    pipelines = {dict(labels)["pipeline"]
                 for (name, labels) in parse_metrics(scrape())
                 if name == PREFIX + FAMILY}
    assert pipelines == {"encode", "rebuild", "verify"}
