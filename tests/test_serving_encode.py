"""Request lines → encoded batch: the servables' one native pass
(``serving/registry.py::_LineEncoder``) against the Python path it replaced
(``_parse_rows`` + ``DatasetEncoder.transform`` + ``_pad_ds``), which stays
its fallback and its oracle.

A good block must encode bit for bit as the oracle encodes it, at every size
a bucket or a bulk block takes; a bad one must reply, or fail with the same
exception, exactly as the oracle does.
"""

import threading

import pytest

from avenir_tpu.core.encoding import DatasetEncoder, EncodedDataset
from avenir_tpu.core.schema import FeatureSchema
from avenir_tpu.datagen.churn import CHURN_SCHEMA_JSON, generate_churn
from avenir_tpu.datagen.elearn import ELEARN_SCHEMA_JSON, generate_elearn
from avenir_tpu.models import knn as mknn
from avenir_tpu.runtime import native
from avenir_tpu.serving.registry import (KNNServable, _LineEncoder, _pad_ds,
                                         _parse_rows)
from avenir_tpu.telemetry import spans as tel

REFS = 2048


@pytest.fixture(scope="module")
def enc():
    assert native.is_available(), native.build_error()
    return DatasetEncoder(FeatureSchema.from_json(ELEARN_SCHEMA_JSON))


def _query_lines(n, seed=5):
    """A day's activity rows as a request carries them: ``userID`` and the
    nine signals, no status column."""
    return [",".join(r[:10]) for r in generate_elearn(n, seed=seed).tolist()]


def _oracle(enc, lines, pad_to):
    rows = _parse_rows(lines, ",", enc.max_ordinal(False))
    return _pad_ds(enc.transform(rows, with_labels=False), pad_to)


def _outcome(fn):
    """What a call gives: ("ok", value) or ("raise", type, message)."""
    try:
        return ("ok", fn())
    except Exception as e:                       # noqa: BLE001 — compared
        return ("raise", type(e), str(e))


def _same_ds(a: EncodedDataset, b: EncodedDataset):
    assert a.codes.dtype == b.codes.dtype and a.cont.dtype == b.cont.dtype
    assert a.codes.shape == b.codes.shape and a.cont.shape == b.cont.shape
    assert a.codes.tobytes() == b.codes.tobytes()
    assert a.cont.tobytes() == b.cont.tobytes()
    assert a.labels is None and b.labels is None
    assert a.valid_rows == b.valid_rows
    assert a.n_bins.tolist() == b.n_bins.tolist()
    assert (a.class_values, a.binned_ordinals, a.cont_ordinals) == \
        (b.class_values, b.binned_ordinals, b.cont_ordinals)


@pytest.fixture(scope="module")
def servable(enc):
    rows = generate_elearn(REFS, seed=3)
    train = DatasetEncoder(FeatureSchema.from_json(ELEARN_SCHEMA_JSON))
    ds = train.fit_transform(rows)
    est = mknn.KNN(k=5, kernel="gaussian")
    return KNNServable(est, est.fit(ds), enc)


@pytest.mark.parametrize("rows", [1, 8, 64, 256, 4096])
def test_native_encode_is_the_python_encode_bit_for_bit(enc, rows):
    lines = _query_lines(rows)
    le = _LineEncoder(enc, ",")
    for pad_to in (rows, rows + 3):
        got = le._native(lines, pad_to)
        assert got is not None                   # the native pass took it
        _same_ds(got, _oracle(enc, lines, pad_to))


@pytest.mark.parametrize("rows", [1, 8, 64, 256, 4096])
def test_knn_replies_equal_through_native_and_forced_fallback(
        servable, rows, monkeypatch):
    lines = _query_lines(rows, seed=11)
    native_out = servable.score_lines(lines, rows)
    monkeypatch.setattr(servable._encode, "specs", None)
    assert servable.score_lines(lines, rows) == native_out


def test_two_threads_share_one_spec(enc):
    le = _LineEncoder(enc, ",")
    blocks = [_query_lines(n, seed=s) for s, n in
              ((1, 4096), (2, 64), (3, 1), (4, 777))]
    serial = [le(b, len(b)) for b in blocks]
    out = {t: [] for t in range(2)}
    start = threading.Barrier(2)

    def work(t):
        start.wait()
        for _ in range(20):
            for i in (range(4) if t == 0 else range(3, -1, -1)):
                out[t].append((i, le(blocks[i], len(blocks[i]))))

    threads = [threading.Thread(target=work, args=(t,)) for t in range(2)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    for t in range(2):
        assert len(out[t]) == 80
        for i, ds in out[t]:
            _same_ds(ds, serial[i])


GOOD = _query_lines(3, seed=21)


def _field(value, at=4):
    parts = GOOD[1].split(",")
    parts[at] = value
    return [GOOD[0], ",".join(parts), GOOD[2]]


MALFORMED = {
    "too_few_fields": ["u1,1,2,3"],
    "ragged_within_call": [GOOD[0], GOOD[1] + ",7", GOOD[2]],
    "narrow_row_after_good": [GOOD[0], "u9,1,2"],
    "blank_line": [GOOD[0], "", GOOD[2]],
    "whitespace_line": [GOOD[0], "   ", GOOD[2]],
    "embedded_newline": [GOOD[0], GOOD[1] + "\n" + GOOD[2]],
    "trailing_newline_in_line": [GOOD[0] + "\n", GOOD[1]],
    "crlf_endings": [ln + "\r" for ln in GOOD],
    "two_crs": [ln + "\r\r" for ln in GOOD],
    "empty_field": _field(""),
    "word": _field("abc"),
    "hex": _field("0x10"),
    "nan_payload": _field("nan(1)"),
    "underscore": _field("1_0"),
    "leading_space": _field(" 3"),
    "trailing_space": _field("3 "),
    "inf": _field("inf"),
    "nan": _field("nan"),
    "exponent": _field("1e2"),
    "lone_surrogate_id": ["\ud800" + GOOD[0][1:]],
    "no_lines": [],
}


@pytest.mark.parametrize("case", sorted(MALFORMED))
def test_malformed_request_fails_or_replies_as_the_python_path(
        enc, servable, case, monkeypatch):
    lines = MALFORMED[case]
    pad_to = max(len(lines), 1)
    le = _LineEncoder(enc, ",")
    got, want = (_outcome(lambda: le(lines, pad_to)),
                 _outcome(lambda: _oracle(enc, lines, pad_to)))
    assert got[0] == want[0], (got, want)
    if got[0] == "ok":
        _same_ds(got[1], want[1])
    else:
        assert got[1:] == want[1:]
    # and through the servable: its reply lines, or its exception
    through = _outcome(lambda: servable.score_lines(lines, pad_to))
    monkeypatch.setattr(servable._encode, "specs", None)
    today = _outcome(lambda: servable.score_lines(lines, pad_to))
    assert through == today


def test_hex_and_nan_payload_refused_by_the_native_kernel_too(enc):
    # strtod reads both; float() does not, and the kernel mirrors float()
    specs = native.EncoderSpecs(enc, with_labels=False, with_ids=False)
    for bad in ("0x10", "nan(1)", "0X1p3"):
        with pytest.raises(ValueError, match="numeric"):
            specs.encode(_field(bad)[1].encode(), 10, rows=1)


@pytest.mark.parametrize("eol", ["\r", "\r\r", "\r\r\r"])
def test_trailing_crs_strip_as_python_before_a_categorical_last_field(eol):
    # the churn rows end in a categorical feature: a carriage return left on
    # it would look up as out-of-vocabulary, not fail
    enc = DatasetEncoder(FeatureSchema.from_json(CHURN_SCHEMA_JSON))
    lines = [",".join(r[:6]) + eol for r in generate_churn(40, seed=2).tolist()]
    le = _LineEncoder(enc, ",")
    got = le._native(lines, 40)
    assert got is not None
    _same_ds(got, _oracle(enc, lines, 40))


def test_fallback_opens_parse_inside_encode(enc):
    le = _LineEncoder(enc, ",")
    tracer = tel.tracer()
    tracer.recorded(clear=True)
    tracer.enable()
    try:
        le(GOOD, 4)
        le(_field("1_0"), 3)                     # native refuses, Python not
    finally:
        tracer.disable()
    rec = tracer.recorded(clear=True)
    encodes = [r for r in rec if r.name == "servable.encode"]
    parses = [r for r in rec if r.name == "servable.parse"]
    assert [(e.attrs["rows"], e.attrs["native_rows"]) for e in encodes] == \
        [(3, 3), (3, 0)]
    assert len(parses) == 1 and parses[0].parent_id == encodes[1].span_id
