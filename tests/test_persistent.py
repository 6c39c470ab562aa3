"""The model blob (`controller/persistent.py`): one protocol-5 pickle of the
model list, its arrays out of band, framed into one bytes object; the
per-model fallback; blobs written before the framed format still load."""

from __future__ import annotations

import pickle
import time
from dataclasses import dataclass

import numpy as np
import pytest

import predictionio_tpu.obs.spans as spans
from predictionio_tpu.controller.persistent import (
    RetrainOnDeploy,
    deserialize_models,
    serialize_models,
)
from predictionio_tpu.obs.registry import get_default_registry
from predictionio_tpu.workflow.core import run_train


@dataclass
class Arrays:
    tables: dict


@dataclass
class Vocab:
    ids: dict


class Unpicklable:
    def __reduce__(self):
        raise pickle.PicklingError("deliberately unserializable")


class CountsPickles:
    calls = 0

    def __init__(self):
        self.factors = np.ones((4, 3), np.float32)

    def __getstate__(self):
        CountsPickles.calls += 1
        return self.__dict__


def _serialized():
    return get_default_registry().counter(
        "persist_serialize_total", labelnames=("path",))


def _tables() -> dict:
    rng = np.random.default_rng(7)
    c_i32 = rng.integers(-5, 9, (6, 5), dtype=np.int32)
    c_f32 = rng.random((5, 4), dtype=np.float32)
    read_only = rng.random((3, 4), dtype=np.float32)
    read_only.flags.writeable = False  # as np.asarray of a device array is
    return {
        "c_i32": c_i32,
        "c_f32": c_f32,
        "f_i32": np.asfortranarray(rng.integers(0, 99, (4, 7), dtype=np.int32)),
        "f_f32": np.asfortranarray(c_f32.T.copy()),
        "sliced_i32": c_i32[:, ::2],
        "sliced_f32": c_f32[::2, 1:],
        "zero_d_i32": np.array(11, np.int32),
        "zero_d_f32": np.array(2.5, np.float32),
        "read_only": read_only,
    }


def test_a_framed_round_trip_keeps_every_array_writable_and_apart():
    tables = _tables()
    attrs: dict = {}
    blob = serialize_models([Arrays(tables)], attrs)
    assert isinstance(blob, bytes) and blob[:1] != b"\x80"
    [back] = deserialize_models(blob)
    stored = np.frombuffer(blob, np.uint8)
    assert set(back.tables) == set(tables)
    for name, want in tables.items():
        got = back.tables[name]
        assert got.dtype == want.dtype and got.shape == want.shape, name
        np.testing.assert_array_equal(got, want, err_msg=name)
        assert got.flags.writeable, name
        assert not np.shares_memory(got, stored), name
        got[...] = 0  # writable in fact, and the blob is untouched
    assert deserialize_models(blob)[0].tables["c_i32"].any()
    # every contiguous array went out of band; the sliced two stayed in
    contiguous = [a for a in tables.values()
                  if a.flags.c_contiguous or a.flags.f_contiguous]
    assert attrs["buffers"] == len(contiguous)
    assert attrs["out_of_band_bytes"] == sum(a.nbytes for a in contiguous)
    assert attrs["bytes"] == len(blob) and attrs["fallback"] == 0


def test_an_object_two_models_share_stays_one_object():
    vocab = Vocab({f"i{i}": i for i in range(100)})
    first, second = Arrays({"v": vocab}), Arrays({"v": vocab})
    a, b = deserialize_models(serialize_models([first, second]))
    assert a.tables["v"] is b.tables["v"]
    assert a.tables["v"] == vocab


def test_a_model_that_cannot_be_pickled_degrades_alone():
    counter = _serialized()
    before = counter.value(path="fallback")
    attrs: dict = {}
    model = Arrays({"x": np.arange(5, dtype=np.int32)})
    blob = serialize_models([model, Unpicklable(), None], attrs)
    back = deserialize_models(blob)
    assert back[1:] == [RetrainOnDeploy(1), RetrainOnDeploy(2)]
    np.testing.assert_array_equal(back[0].tables["x"], model.tables["x"])
    assert attrs["fallback"] == 1 and attrs["buffers"] == 1
    assert counter.value(path="fallback") == before + 1


def test_a_blob_in_the_earlier_plain_pickle_still_loads():
    tables = _tables()
    legacy = pickle.dumps([Arrays(tables), RetrainOnDeploy(1)], protocol=4)
    [back, marker] = deserialize_models(legacy)
    assert marker == RetrainOnDeploy(1)
    for name, want in tables.items():
        np.testing.assert_array_equal(back.tables[name], want, err_msg=name)
        assert back.tables[name].flags.writeable, name


def test_an_unknown_format_version_is_refused():
    blob = bytearray(serialize_models([Arrays({})]))
    blob[8] = 99  # the version, just after the magic
    with pytest.raises(ValueError, match="format 99"):
        deserialize_models(bytes(blob))


def test_the_common_path_pickles_each_model_once():
    counter = _serialized()
    before = counter.value(path="one_pass")
    CountsPickles.calls = 0
    [back] = deserialize_models(serialize_models([CountsPickles()]))
    assert CountsPickles.calls == 1
    assert counter.value(path="one_pass") == before + 1
    np.testing.assert_array_equal(back.factors, np.ones((4, 3), np.float32))


VARIANT = {
    "id": "default",
    "engineFactory": "sample_engine.Engine0Factory",
    "datasource": {"params": {"id": 1}},
    "preparator": {"params": {"id": 2}},
    "algorithms": [{"name": "algo0", "params": {"id": 3}}],
    "serving": {},
}


def test_a_train_job_reports_how_its_model_was_serialized(fresh_storage):
    counter = _serialized()
    before = counter.value(path="one_pass")
    since = time.time()
    inst = run_train(fresh_storage, VARIANT)
    [sp] = [s for s in spans.get_default_recorder().recent(since)
            if s.name == "persist.serialize"]
    blob = fresh_storage.get_model_data_models().get(inst.id).models
    assert sp.attrs["bytes"] == len(blob)
    assert sp.attrs["fallback"] == 0
    assert sp.attrs["buffers"] == 0 and sp.attrs["out_of_band_bytes"] == 0
    assert counter.value(path="one_pass") == before + 1
    assert [m.algo_id for m in deserialize_models(blob)] == [3]
