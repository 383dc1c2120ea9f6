"""The contract between the harness and what is added to it as files
(``benchmark/README.md``, "Add, as files only"). No JAX, seconds.

The scores pinned here are those ``harness/reference.py`` gave at the
commit before the references became files (PR 26's tree), on the first
three queries of seed 2**31 + 27 over 200 passages: moving the BM25
into ``references/bm25.py`` changed not a digit."""

import importlib
import os
import re

import pytest

import references
from harness import manifest_check, work
from harness.comparison import Comparison

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
PINNED = [  # terms, hits.total, top-3 ids, their scores, sum of df
    ([1632, 53, 3, 8, 26], 177, [193, 11, 4],
     [4.820992946624756, 4.038966178894043, 4.021212100982666], 315),
    ([1, 8, 46, 6, 0, 4, 28], 200, [186, 199, 162],
     [6.303426265716553, 4.588386058807373, 4.532353401184082], 829),
    ([105, 91, 14, 55, 284], 106, [122, 21, 53],
     [9.420923233032227, 5.227451801300049, 4.905762195587158], 132)]
PEAKS = {"hbm_bytes_per_s": 800e9, "bf16_flops_per_s": 200e12}


def _configs():
    return sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "configs")))


@pytest.fixture(scope="module")
def passages():
    config = manifest_check.load_json(
        os.path.join(BENCH, "configs", "msmarco-passage.json"))
    config["docs"] = 200
    gen = importlib.import_module(f"generators.{config['generator']}")
    dataset = gen.Dataset(config, 2**31 + 27, 2)
    return config, dataset, references.build(config, dataset.view(200))


def _ref(terms, size=3):
    return {"kind": "match", "field": "text", "terms": terms, "size": size,
            "aggs": {}}


def _answer(total, ids, scores):
    return {"total": total, "ids": ids, "scores": scores, "aggs": {}}


def test_manifest_is_sound():
    assert manifest_check.check(ROOT) == []


@pytest.mark.parametrize("name", _configs())
def test_every_configurations_reference_resolves(name):
    config = manifest_check.load_json(
        os.path.join(BENCH, "configs", f"{name}.json"))
    path = os.path.join(BENCH, "references", f"{config['reference']}.py")
    assert os.path.isfile(path)
    assert not manifest_check.imports_program(path)
    cls = references.named_by(config)
    assert cls.controls and all(isinstance(c, str) for c in cls.controls)
    assert "lost_ack" not in cls.controls  # the harness's own
    for method in ("compare", "work"):
        assert callable(getattr(cls, method))


@pytest.mark.parametrize("terms,total,ids,scores,df_sum", PINNED)
def test_bm25_gives_the_parents_scores(passages, terms, total, ids, scores,
                                       df_sum):
    config, dataset, reference = passages
    assert dataset.queries[:3] == [p[0] for p in PINNED]
    cmp = Comparison(config["limits"])
    reference.compare(cmp, "q", _answer(total, ids, scores), _ref(terms))
    assert cmp.correct(), cmp.numbers()
    assert all(n["value"] == 0 for n in cmp.numbers().values())


@pytest.mark.parametrize("terms,total,ids,scores,df_sum", PINNED)
def test_the_bfloat16_control_fails_the_limit(passages, terms, total, ids,
                                              scores, df_sum):
    config, _, reference = passages
    cmp = Comparison(config["limits"])
    reference.compare(cmp, "q", _answer(total, ids, scores),
                      _ref(terms), control="bfloat16")
    worst = cmp.numbers()["score_rel_err"]
    assert not cmp.correct() and worst["value"] > 3 * worst["limit"]


def test_an_answer_a_digit_off_is_seen(passages):
    config, _, reference = passages
    terms, total, ids, scores, _ = PINNED[0]
    cmp = Comparison(config["limits"])
    reference.compare(cmp, "q", _answer(
        total, ids, [scores[0] * 1.001] + scores[1:]), _ref(terms))
    assert cmp.numbers()["score_rel_err"]["value"] == pytest.approx(
        1e-3, rel=1e-3)
    assert not cmp.correct()


def test_a_number_without_a_limit_is_refused():
    with pytest.raises(KeyError):
        Comparison({"bad_hits": 0}).note("score_rel_err", 0.0, "q")


@pytest.mark.parametrize("terms,total,ids,scores,df_sum", PINNED)
def test_work_of_a_match_is_its_postings(passages, terms, total, ids, scores,
                                         df_sum):
    _, _, reference = passages
    got = reference.work(_ref(terms))
    assert got["bytes"] == 8 * df_sum and got["flops"] == df_sum
    assert got["peak"] in work.peaks_for("TPU v5 lite")
    # one add to 8 bytes: the bytes set the bound on any chip in the table
    assert work.least_seconds(got, PEAKS, 1) == 8 * df_sum / 800e9


@pytest.mark.parametrize("case,want", [
    ({"bytes": 8e9, "flops": 0}, 0.01),                      # no operations
    ({"bytes": 8e9, "flops": 1e12,
      "peak": "bf16_flops_per_s"}, 0.01),                    # bytes-bound
    ({"bytes": 8e6, "flops": 4e12,
      "peak": "bf16_flops_per_s"}, 0.02),                    # MXU-bound
])
def test_least_seconds_is_the_larger_of_the_two_bounds(case, want):
    assert work.least_seconds(case, PEAKS, 1) == pytest.approx(want)
    assert work.least_seconds(case, PEAKS, 4) == pytest.approx(want / 4)


def test_operations_without_a_peak_are_an_error():
    with pytest.raises(KeyError):
        work.least_seconds({"bytes": 1, "flops": 1}, PEAKS, 1)
    with pytest.raises(KeyError):
        work.peaks_for("TPU v9")


def test_only_the_server_imports_the_program():
    """The yardstick takes from the program the system under test and
    nothing else: ``harness/server.py`` starts it; tests may break it."""
    offenders = []
    for folder, _, files in os.walk(BENCH):
        if ".cache" in folder or folder.startswith(
                os.path.join(BENCH, "tests")):
            continue
        for name in files:
            path = os.path.join(folder, name)
            if name.endswith(".py") and manifest_check.imports_program(path):
                offenders.append(os.path.relpath(path, BENCH))
    # server.py imports it inside functions: indented, so found as well
    assert offenders == ["harness/server.py"]


def test_a_reference_that_is_absent_or_imports_the_program_is_refused(
        tmp_path, monkeypatch):
    manifest = manifest_check.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    file = manifest["configs"][0]["file"]
    plain = manifest_check.load_json

    def naming(reference):
        def load_json(path):
            held = plain(path)
            if path.endswith(file):
                held["reference"] = reference
            return held
        return load_json

    monkeypatch.setattr(manifest_check, "load_json", naming("absent"))
    assert any("no reference" in f for f in manifest_check.check(ROOT,
                                                                 manifest))
    bad = tmp_path / "leaky.py"
    bad.write_text("import numpy\nfrom elasticsearch_tpu.ops import x\n")
    assert manifest_check.imports_program(str(bad))
    assert not manifest_check.imports_program(
        os.path.join(BENCH, "references", "bm25.py"))


def test_the_command_takes_a_control_as_a_free_string():
    import run as bench_run

    assert bench_run.parse_args(["--control", "int4"]).control == "int4"
    text = open(os.path.join(BENCH, "run.py"), encoding="utf-8").read()
    assert not re.search(r'choices=\("bfloat16"', text)
