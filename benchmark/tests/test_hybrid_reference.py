"""The hybrid cell's comparison (``references/hybrid.py``) at a size a
test can hold: the reference's own answers pass, each of its four
controls fails by one of the configuration's limits, an answer with a
winner's BM25 part dropped (the fusion of two separate top-10 lists)
fails, a tie at the k-th neighbour may fall either way, and the
generator's bulk lines carry the view's text and vectors."""

import importlib
import json
import os

import numpy as np
import pytest

from harness import manifest_check
from harness.comparison import Comparison
from references import hybrid

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2**31 + 41


@pytest.fixture(scope="module")
def cell():
    config = manifest_check.load_json(os.path.join(
        ROOT, "benchmark", "configs", "msmarco-passage-hybrid.json"))
    config.update(docs=1500, requests=48)
    gen = importlib.import_module(f"generators.{config['generator']}")
    dataset = gen.Dataset(config, SEED, 2)
    view = dataset.view(dataset.n_docs)
    refs = [req["ref"] for req in dataset.operations()["hybrid_top10"]]
    return config, dataset, view, refs, hybrid.Reference(view, config)


def _own_answer(reference, ref):
    scores, matched = reference._lexical(ref)
    return reference._answer_of(ref, scores, matched,
                                reference._winner_sets(ref)[0])


def _compare(config, reference, refs, answers=None, control=None):
    cmp = Comparison(config["limits"])
    for n, ref in enumerate(refs):
        answer = None if answers is None else answers[n]
        reference.compare(cmp, f"q{n}", answer, ref, control=control)
    return cmp


def test_the_reference_in_the_programs_place_is_correct(cell):
    config, _, _, refs, reference = cell
    answers = [_own_answer(reference, ref) for ref in refs]
    # rendered as the REST layer renders an exact total, or bare
    for a in answers[::2]:
        a["total"] = {"value": a["total"], "relation": "eq"}
    cmp = _compare(config, reference, refs, answers)
    assert cmp.correct(), cmp.numbers()
    assert cmp.compared == len(refs)


@pytest.mark.parametrize("control", hybrid.Reference.controls)
def test_each_control_is_not_correct(cell, control):
    config, _, _, refs, reference = cell
    numbers = _compare(config, reference, refs, control=control).numbers()
    over = [n for n, v in numbers.items() if v["value"] > v["limit"]]
    assert over, numbers


def test_a_winner_scored_without_its_text_is_not_correct(cell):
    """The parent's fault: the winners past a lexical window of 10 lose
    their BM25 part, and the total is the larger side's."""
    config, _, _, refs, reference = cell
    low = _compare(config, reference, refs, control="lexical_window")
    assert low.numbers()["total_abs_diff"]["value"] > 0
    assert low.numbers()["score_rel_err"]["value"] > 1e-3


def test_a_tie_at_the_kth_neighbour_may_fall_either_way(cell):
    """The (k+1)-th neighbour moved within ``KNN_TIE`` of the k-th: an
    answer holding either of the two is correct."""
    config, _, _, refs, reference = cell
    ref = refs[0]
    saved = dict(reference.knn._top)
    ids, sims = (a.copy() for a in saved[None])
    sims[ref["n"], ref["k"]] = sims[ref["n"], ref["k"] - 1] * (
        1 - 0.5 * hybrid.KNN_TIE)
    try:
        reference.knn._top = {None: (ids, sims)}
        reference._last = (None, None)
        sets = reference._winner_sets(ref)
        assert len(sets) == 2
        for winners in sets:
            scores, matched = reference._lexical(ref)
            answer = reference._answer_of(ref, scores, matched, winners)
            cmp = Comparison(config["limits"])
            reference.compare(cmp, "tie", answer, ref)
            assert cmp.correct(), cmp.numbers()
    finally:
        reference.knn._top = saved
        reference._last = (None, None)


def test_the_bulk_lines_carry_the_views_text_and_vectors(cell):
    _, dataset, view, refs, _ = cell
    lo = 100
    lines = dataset.bulk_body(lo, lo + 30).split("\n")
    assert len(lines) == 61 and lines[-1] == ""
    field = view["text_fields"]["text"]
    for j in range(0, 30, 7):
        doc = json.loads(lines[2 * j + 1])
        np.testing.assert_array_equal(np.asarray(doc["emb"], np.float32),
                                      view["vectors"][lo + j])
        assert doc["text"] == " ".join(
            f"t{int(t)}" for t in dataset.label[field.doc_tokens(lo + j)])
    body = json.loads(json.dumps(
        dataset.operations()["hybrid_top10"][3]["body"]))
    np.testing.assert_array_equal(
        np.asarray(body["knn"]["query_vector"], np.float32),
        view["queries"][3])
    assert body["knn"]["boost"] == 10 and body["_source"] is False
    # the same passages and queries as msmarco-serial's, seed for seed
    from generators import msmarco_passage

    text = manifest_check.load_json(os.path.join(
        ROOT, "benchmark", "configs", "msmarco-passage.json"))
    text["docs"] = dataset.n_docs
    plain = msmarco_passage.Dataset(text, SEED, 2)
    for i in (0, 777):
        assert json.loads(plain.bulk_body(i, i + 1).split("\n")[1])[
            "text"] == json.loads(dataset.bulk_body(i, i + 1).split(
                "\n")[1])["text"]
    assert plain.queries[:len(refs)] == [r["terms"] for r in refs]
