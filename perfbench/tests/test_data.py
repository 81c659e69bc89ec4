"""(a) The seed-made reference arrays are what the program's CSV path gives
for the same rows, field for field."""

import json

import numpy as np

from lib import data


def test_refs_equal_encode_input_of_their_csv(tmp_path):
    from avenir_tpu.core.config import JobConfig
    from avenir_tpu.jobs.base import Job

    config = json.load(open(
        f"{__import__('conftest').PERFBENCH}/configs/elearn_knn.json"))
    cont, labels = data.make_refs(3000, seed=2 ** 31 + 7)
    schema = tmp_path / "elearn.json"
    schema.write_text(json.dumps(config["schema"]))
    train = tmp_path / "train.csv"
    train.write_text("\n".join(data.refs_as_csv_lines(cont, labels)) + "\n")
    conf = JobConfig({"feature.schema.file.path": str(schema)})
    for need_rows in (True, False):          # Python path and native path
        _enc, ds, _rows = Job.encode_input(conf, str(train),
                                           need_rows=need_rows)
        assert ds.codes.shape == (3000, 0)
        np.testing.assert_array_equal(ds.cont, cont)
        np.testing.assert_array_equal(ds.labels, labels)
        assert list(ds.class_values) == list(data.CLASS_VALUES)


def test_same_seed_same_inputs_and_streams_differ():
    a = data.make_refs(5000, seed=3)
    b = data.make_refs(5000, seed=3, threads=1)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    assert data.make_query_lines(50, 3) == data.make_query_lines(50, 3)
    assert data.make_query_lines(50, 3) != data.make_query_lines(50, 4)
    first = data.make_query_lines(1, 3)[0].split(",")
    assert len(first) == 10
    assert [float(x) for x in first[1:]] != a[0][0].tolist()


def test_distributions_are_the_generators():
    """Same marginals as the program's generator (a different stream, so
    compared by moments)."""
    from avenir_tpu.datagen.elearn import generate_elearn

    cont, labels = data.make_refs(40000, seed=11)
    rows = generate_elearn(40000, seed=11)
    theirs = rows[:, 1:10].astype(np.float64)
    np.testing.assert_allclose(cont.mean(0), theirs.mean(0), rtol=0.03)
    np.testing.assert_allclose(cont.std(0), theirs.std(0), rtol=0.03)
    assert abs(labels.mean() - (rows[:, 10] == "F").mean()) < 0.02
