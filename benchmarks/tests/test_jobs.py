"""Each job file: the reference passes its own comparison, the control
(the reference in the precision below the configuration's) fails it,
``min_bytes`` equals a hand count, and the tables follow the seed."""

import json
import os

import numpy as np
import pytest

import run

# every job file at a size a test holds; wordcount needs its top words
# above 256, where bfloat16 stops holding every count, as at a cell's size
SMALL = {
    "sort": {"rows": 4096},
    "wordcount": {"rows": 1 << 16, "vocab": 256, "top": 20},
    "groupby": {"rows": 4096, "groups": 64},
    "join_topk": {"rows": 4096, "dim_rows": 128, "top": 100, "expansion": 1.25},
}
JOBS = sorted(SMALL)


def load(job):
    return run.load_module("jobs", job), dict(SMALL[job])


def traffic(name):
    with open(os.path.join(run.HERE, "traffic", name + ".json")) as fh:
        params = json.load(fh)
    return run.load_module("jobs", params["job"]), params


def reference_answer(job, table, params):
    """What a correct program returns, from the table's stored answer."""
    name = job.__name__.rsplit("_jobs_", 1)[1]
    if name == "sort":
        return {"key": table["want_key"], "payload": job.key_payload(table["want_key"])}
    if name == "groupby":
        slot = np.flatnonzero(table["want_c"])
        return {"k": (slot - 1).astype(np.int32),
                "c": table["want_c"][slot].astype(np.int32),
                "s": table["want_s"][slot].astype(np.float32)}
    if name == "wordcount":
        order = np.argsort(-table["want"], kind="stable")[: params["top"]]
        return {"word": np.array([f"w{i:05d}" for i in order], object),
                "count": table["want"][order]}
    return {k: v.copy() for k, v in table["want"].items()}


@pytest.mark.parametrize("name", JOBS)
@pytest.mark.parametrize("seed", [1, 2**31 + 12345, 4000000007])
def test_reference_passes_and_control_fails(name, seed, tmp_path):
    job, params = load(name)
    table = job.make_table(np.random.default_rng([seed, 0]), params, str(tmp_path), 0)
    sound = job.compare(table, reference_answer(job, table, params), params)
    assert all(value <= limit for value, limit in sound.values()), sound
    control = job.compare(table, job.control(table, params), params)
    assert any(value > limit for value, limit in control.values()), control


@pytest.mark.parametrize("name", JOBS)
def test_tables_follow_the_seed(name, tmp_path):
    job, params = load(name)

    def arrays(seed, index):
        t = job.make_table(np.random.default_rng([seed, index]), params,
                           str(tmp_path), index)
        if "path" in t:
            with open(t["path"], "rb") as fh:
                return [np.frombuffer(fh.read(), np.uint8)]
        return [a for key in ("arrays", "fact", "dim") if key in t
                for a in t[key].values()]

    same = zip(arrays(7, 0), arrays(7, 0))
    assert all(np.array_equal(a, b) for a, b in same)
    assert not all(np.array_equal(a, b) for a, b in zip(arrays(7, 0), arrays(8, 0)))
    assert not all(np.array_equal(a, b) for a, b in zip(arrays(7, 0), arrays(7, 1)))


def test_min_bytes_hand_counts():
    """The bytes a query must read and write once, counted by hand: at
    the cells' own sizes, and for the two job files that wait for a
    cell at the sizes they were timed at in PR 23."""
    job, p = traffic("sort")
    # 2^25 rows x (4 B key + 4 B payload), read once and written once
    assert job.min_bytes(p) == 2 * 8 * 2**25 == 536_870_912
    assert job.input_rows(p) == 2**25
    job, p = traffic("wordcount")
    # 2^23 words x four u32 physical columns in; 20 rows x (16 + 4) B out
    assert job.min_bytes(p) == 16 * 2**23 + 20 * 20 == 134_218_128
    assert job.input_rows(p) == 2**23
    job, _ = load("groupby")
    p = {"rows": 2**24, "groups": 2**20}
    # 2^24 rows x 8 B in; 2^20 groups x (k + c + s) x 4 B out
    assert job.min_bytes(p) == 8 * 2**24 + 12 * 2**20 == 146_800_640
    assert job.input_rows(p) == 2**24
    job, _ = load("join_topk")
    p = {"rows": 2**21, "dim_rows": 2**16, "top": 100}
    # (2^21 + 2^16) rows x 8 B in; 100 rows x 4 columns x 4 B out
    assert job.min_bytes(p) == 8 * (2**21 + 2**16) + 1600 == 17_303_104
    assert job.input_rows(p) == 2**21 + 2**16
