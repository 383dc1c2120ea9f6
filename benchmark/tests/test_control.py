"""The comparison that decides ``correct``, at a size a test can hold:
the reference in the program's place passes, the control (the reference
computed in bfloat16, the nearest precision below the float32 the
configuration's scores are stated in) does not, and neither does an
answer altered where it is produced."""

import importlib
import os

import numpy as np
import pytest

import references
from harness import manifest_check, run_cell
from harness.comparison import Comparison
from references import bm25

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _dataset(name, docs=3000, seed=2**31 + 11):
    config = manifest_check.load_json(
        os.path.join(ROOT, "benchmark", "configs", f"{name}.json"))
    config["docs"] = docs
    if "append_pool_docs" in config["generator_params"]:
        config["generator_params"]["append_pool_docs"] = 1000
    gen = importlib.import_module(f"generators.{config['generator']}")
    return config, gen.Dataset(config, seed, 2)


def _served_by_reference(dataset, refs, precision, n=64):
    """Window records as the reference itself would have answered."""
    view = dataset.view(dataset.n_docs)
    records, bm25s = [], {}
    ops = sorted({rid[0] for rid in refs})
    for rid in [(op, i) for i in range(n // len(ops)) for op in ops]:
        ref = refs[rid]
        if ref["field"] not in bm25s:
            bm25s[ref["field"]] = bm25.Bm25(
                view["text_fields"][ref["field"]], view["shard"], 2)
        bm = bm25s[ref["field"]]
        scores, matched = bm.match(ref["terms"], precision=precision)
        ids, top = bm25.top_k(scores, matched, ref["size"])
        records.append({
            "id": list(rid), "kind": "search", "status": 200,
            "total": int(matched.sum()), "ids": [str(i) for i in ids],
            "scores": top.tolist(),
            "aggs": {name: [[k, c] for k, c in bm25.bucket_counts(
                spec, view["columns"], matched).items()]
                for name, spec in ref["aggs"].items()}})
    return view, records


def _compare(config, records, refs, view, control=None):
    cmp = Comparison(config["limits"])
    reference = references.build(config, view)
    run_cell.compare_searches(
        cmp, records, refs, reference,
        lambda ref: reference.work(ref)["bytes"], 1, 10_000, control=control)
    return cmp


def _refs(dataset):
    return {(op, i): req["ref"]
            for op, reqs in dataset.operations().items()
            for i, req in enumerate(reqs)}


@pytest.mark.parametrize("name", ["msmarco-passage", "http-logs"])
def test_reference_passes_and_the_bfloat16_control_fails(name):
    config, dataset = _dataset(name)
    refs = _refs(dataset)
    for precision, want in (("float32", True), ("bfloat16", False)):
        view, records = _served_by_reference(dataset, refs, precision)
        cmp = _compare(config, records, refs, view)
        assert cmp.correct() is want, cmp.numbers()
        if not want:
            worst = cmp.numbers()["score_rel_err"]
            assert worst["value"] > 3 * worst["limit"]


def test_control_flag_puts_the_control_in_the_programs_place():
    config, dataset = _dataset("msmarco-passage")
    refs = _refs(dataset)
    view, records = _served_by_reference(dataset, refs, "float32")
    assert not _compare(config, records, refs, view,
                        control="bfloat16").correct()


@pytest.mark.parametrize("fault", ["score", "swapped_hit", "total",
                                   "bucket", "dropped_hit"])
def test_an_altered_answer_is_not_correct(fault):
    config, dataset = _dataset("http-logs")
    refs = _refs(dataset)
    view, records = _served_by_reference(dataset, refs, "float32")
    hit = next(r for r in records if len(r["ids"]) == 10)
    agg = next(r for r in records if r["aggs"])
    if fault == "score":
        hit["scores"][3] *= 1.002
    elif fault == "swapped_hit":
        matched = set(np.flatnonzero(bm25.Bm25(
            view["text_fields"]["request"], view["shard"], 2).matched(
                refs[tuple(hit["id"])]["terms"])).tolist())
        hit["ids"][9] = str(next(i for i in range(dataset.n_docs)
                                 if i not in matched))
    elif fault == "total":
        hit["total"] += 1
    elif fault == "bucket":
        agg["aggs"]["status"][0][1] += 1
    elif fault == "dropped_hit":
        hit["ids"].pop()
        hit["scores"].pop()
    assert not _compare(config, records, refs, view).correct()


def test_a_run_that_compared_nothing_is_not_correct():
    config, _ = _dataset("msmarco-passage", docs=200)
    assert not Comparison(config["limits"]).correct()


def test_bf16_rounding_is_round_to_nearest_even():
    x = np.asarray([1.0, 1.00390625, 1.005859375, 3.14159], np.float32)
    got = bm25.bf16(x)
    assert got[0] == 1.0 and got[1] == 1.0  # tie to even
    assert got[2] == np.float32(1.0078125)
    assert abs(got[3] - 3.14159) < 3.14159 * 2 ** -8
