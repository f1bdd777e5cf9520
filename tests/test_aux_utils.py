"""Aux subsystem tests: preprocessors, distributions, profiling, metrics,
collections, sentiment."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn import conf as C
from deeplearning4j_tpu.nn import preprocessors as pp
from deeplearning4j_tpu.nlp.sentiment import SentiWordNet
from deeplearning4j_tpu.utils import distributions as dist
from deeplearning4j_tpu.utils.collections_util import (
    MultiDimensionalMap,
    SummaryStatistics,
    extract_archive,
)
from deeplearning4j_tpu.utils.metrics import MetricsIterationListener, MetricsWriter
from deeplearning4j_tpu.utils.profiling import StopWatch, timed


def test_preprocessors():
    x = jnp.arange(12.0).reshape(2, 6)
    assert pp.get("reshape:2,3")(x).shape == (2, 2, 3)
    assert pp.get("flatten")(pp.get("reshape:2,3")(x)).shape == (2, 6)
    z = pp.get("zero_mean_unit_variance")(x)
    assert jnp.allclose(z.mean(0), 0.0, atol=1e-5)
    probs = jnp.full((4, 3), 0.5)
    s = pp.get("binomial_sampling")(probs, jax.random.key(0))
    assert set(np.unique(np.asarray(s))) <= {0.0, 1.0}
    # deterministic eval pass-through
    assert jnp.allclose(pp.get("binomial_sampling")(probs, None), probs)


def test_preprocessors_in_network():
    from deeplearning4j_tpu.models import MultiLayerNetwork

    mc = C.list_builder(
        C.LayerConfig(activation="tanh"), sizes=[4], n_in=6, n_out=2,
        pretrain=False, backward=True,
    )
    mc.preprocessors = {0: "zero_mean_unit_variance"}
    mc2 = C.MultiLayerConfig.from_json(mc.to_json())
    assert mc2.preprocessors == {0: "zero_mean_unit_variance"}
    net = MultiLayerNetwork(mc, seed=0)
    net.init()
    out = net.output(np.random.default_rng(0).normal(2.0, 3.0, (8, 6)).astype(np.float32))
    assert out.shape == (8, 2)


def test_distributions():
    key = jax.random.key(0)
    n = dist.get("normal", 1.0, 0.5)(key, (2000,))
    assert abs(float(n.mean()) - 1.0) < 0.05
    u = dist.get("uniform", -2, 2)(key, (1000,))
    assert float(u.min()) >= -2 and float(u.max()) <= 2
    b = dist.get("binomial", 1, 0.3)(key, (3000,))
    assert abs(float(b.mean()) - 0.3) < 0.05


def test_stopwatch_and_timed():
    sw = StopWatch()
    with sw.lap():
        sum(range(1000))
    assert sw.total > 0 and len(sw.laps) == 1
    records = []
    with timed("x", sink=lambda label, dt: records.append((label, dt))):
        pass
    assert records and records[0][0] == "x"


def test_metrics_writer_and_listener(tmp_path):
    w = MetricsWriter(tmp_path / "m.jsonl")
    listener = MetricsIterationListener(w)
    for i in range(3):
        listener.iteration_done({"iteration": i, "score": 1.0 / (i + 1)})
    w.close()
    recs = MetricsWriter.read(tmp_path / "m.jsonl")
    scores = [r for r in recs if r["tag"] == "train/score"]
    assert len(scores) == 3 and scores[-1]["value"] == pytest.approx(1 / 3)


def test_collections_util(tmp_path):
    m = MultiDimensionalMap()
    m.put("a", 1, "x")
    assert m.get("a", 1) == "x" and m.contains("a", 1) and len(m) == 1

    s = SummaryStatistics()
    for v in [1.0, 2.0, 3.0, 4.0]:
        s.add(v)
    assert s.mean == pytest.approx(2.5)
    assert s.std == pytest.approx(np.std([1, 2, 3, 4], ddof=1))
    assert s.min == 1.0 and s.max == 4.0

    import tarfile

    archive = tmp_path / "a.tar.gz"
    (tmp_path / "payload.txt").write_text("hi")
    with tarfile.open(archive, "w:gz") as t:
        t.add(tmp_path / "payload.txt", arcname="payload.txt")
    out = extract_archive(archive, tmp_path / "out")
    assert (out / "payload.txt").read_text() == "hi"


def test_sentiment_scoring():
    s = SentiWordNet()
    assert s.score("a great wonderful movie") > 0.5
    assert s.score("an awful terrible film") < -0.5
    assert s.verdict("this was great and amazing") in ("positive", "strong_positive")
    assert s.verdict("the plot was awful") in ("negative", "strong_negative")
    assert s.verdict("the chair is wooden") == "neutral"
    # negation flips polarity
    assert s.score("not good") < 0


def test_sentiwordnet_file_loader(tmp_path):
    f = tmp_path / "swn.txt"
    f.write_text(
        "# comment\n"
        "a\t1\t0.75\t0\tgood#1 fine#2\tgloss\n"
        "a\t2\t0\t0.875\tbad#1\tgloss\n"
    )
    s = SentiWordNet.from_sentiwordnet_file(f)
    assert s.lexicon["good"] == pytest.approx(0.75)
    assert s.lexicon["bad"] == pytest.approx(-0.875)


def test_string_utils_edit_distance_and_lcs():
    from deeplearning4j_tpu.utils.string_utils import (
        edit_distance,
        longest_common_substring,
        ngrams,
    )

    assert edit_distance("kitten", "sitting") == 3
    assert edit_distance("", "abc") == 3
    assert edit_distance("same", "same") == 0
    assert longest_common_substring("deeplearning", "earnings") == "earning"
    assert longest_common_substring("abc", "xyz") == ""
    assert ngrams(["a", "b", "c"], 2) == [("a", "b"), ("b", "c")]
    assert ngrams(["a"], 2) == []


def test_s3_and_gcs_savers_via_injected_clients(tmp_path):
    """The object-store savers' logic (key joining, URI rendering, body
    round-trip) exercised offline through injected fakes implementing
    the boto3 / google-cloud-storage surfaces the savers touch."""
    import io

    from deeplearning4j_tpu.utils.cloud_io import GCSModelSaver, S3ModelSaver

    class FakeS3:
        def __init__(self):
            self.store = {}

        def put_object(self, Bucket, Key, Body):
            self.store[(Bucket, Key)] = bytes(Body)

        def get_object(self, Bucket, Key):
            return {"Body": io.BytesIO(self.store[(Bucket, Key)])}

    s3 = S3ModelSaver("models", prefix="runs/a/", client=FakeS3())
    uri = s3.save(b"weights-blob", "ckpt_5.npz")
    assert uri == "s3://models/runs/a/ckpt_5.npz"
    assert s3.load("ckpt_5.npz") == b"weights-blob"

    class FakeBlob:
        def __init__(self, store, key):
            self.store, self.key = store, key

        def upload_from_string(self, data):
            self.store[self.key] = (
                data if isinstance(data, bytes) else data.encode()
            )

        def download_as_bytes(self):
            return self.store[self.key]

    class FakeBucket:
        name = "models"

        def __init__(self):
            self.store = {}

        def blob(self, key):
            return FakeBlob(self.store, key)

    gcs = GCSModelSaver("models", prefix="runs/b", bucket_client=FakeBucket())
    uri = gcs.save(b"gcs-blob", "final.npz")
    assert uri == "gs://models/runs/b/final.npz"
    assert gcs.load("final.npz") == b"gcs-blob"


def test_compile_cache_is_placed_from_outside(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: nothing is set in code. Unset: the
    cache is <checkout>/.jax_cache — a fixed path, no temp names."""
    from pathlib import Path

    import jax

    from deeplearning4j_tpu.utils.compile_cache import use_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert use_compile_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        checkout = Path(__file__).resolve().parent.parent
        assert use_compile_cache() == str(checkout / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            checkout / ".jax_cache"
        )
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
