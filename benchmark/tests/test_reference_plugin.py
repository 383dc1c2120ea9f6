"""What the next ``model_config`` PR will do, rehearsed: a second
reference, a generator, a configuration, a cell and a per-layer metric
added AS FILES to a copy of the tree, driven through the harness as it
stands: ``correct: true``, its control ``correct: false``, an unknown
control refused, and not one file of ``benchmark/harness``,
``benchmark/readers`` or ``benchmark/run.py`` touched.

The reference is ``constant_score``: a ``constant_score`` query over a
``match`` filter scores every matching document 1.0; its control
``plus_one`` puts 2.0 in the program's place. Three rehearsed runs on
the CPU, about two minutes."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "msmarco-constant-serial"

REFERENCE = '''
"""constant_score over a match filter: 1.0 for every document that
holds one of the terms; any k of them are a top-k."""

import numpy as np


class Reference:
    controls = ("plus_one",)

    def __init__(self, view, config):
        self.field = view["text_fields"][config["generator_params"]["field"]]

    def _matched(self, ref):
        out = np.zeros(self.field.n_docs, bool)
        for t in ref["terms"]:
            out[self.field.postings(int(t))[0]] = True
        return out

    def compare(self, cmp, what, answer, ref, control=None, among=None):
        matched = self._matched(ref)
        cmp.compared += 1
        scores = [2.0] * len(answer["scores"]) if control == "plus_one" \\
            else answer["scores"]
        ids = [i for i in answer["ids"] if 0 <= i < len(matched)]
        cmp.note("total_abs_diff", abs(answer["total"] - int(matched.sum())),
                 what)
        cmp.note("bad_hits", abs(len(answer["ids"]) - min(
            ref["size"], int(matched.sum()))) + len(answer["ids"])
            - len(set(ids)) + int((~matched[ids]).sum()), what)
        cmp.note("constant_abs_err",
                 max((abs(s - 1.0) for s in scores), default=0.0), what)

    def work(self, ref):
        return {"bytes": 4 * int(self.field.doc_freq(ref["terms"]).sum()),
                "flops": 0}
'''

GENERATOR = '''
"""The passages of msmarco_passage, asked with constant_score."""

from generators import msmarco_passage


class Dataset(msmarco_passage.Dataset):
    def operations(self):
        ops = super().operations()["match_top10"]
        for op in ops:
            op["body"]["query"] = {"constant_score": {
                "filter": op["body"]["query"]}}
            op["ref"]["kind"] = "constant_score"
        return {"match_top10": ops}
'''


def _write(path, text):
    with open(path, "w", encoding="utf-8") as f:
        f.write(text.lstrip("\n"))


def _load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of what the benchmark is made of, under git, with the new
    cell's files added and the manifest's entries appended."""
    root = str(tmp_path_factory.mktemp("files_only"))
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for program in ("elasticsearch_tpu", "native"):
        os.symlink(os.path.join(ROOT, program), os.path.join(root, program))
    git = ["git", "-C", root, "-c", "user.name=t", "-c", "user.email=t@t"]
    subprocess.run(git + ["init", "-q"], check=True)
    subprocess.run(git + ["add", "BENCHMARK.json", "benchmark"], check=True)
    subprocess.run(git + ["commit", "-q", "-m", "as it stands"], check=True)

    bench = os.path.join(root, "benchmark")
    _write(os.path.join(bench, "references", "constant_score.py"), REFERENCE)
    _write(os.path.join(bench, "generators", "constant_passages.py"),
           GENERATOR)
    config = _load(os.path.join(bench, "configs", "msmarco-passage.json"))
    config.update(name="msmarco-constant", generator="constant_passages",
                  reference="constant_score",
                  limits={"total_abs_diff": 0, "bad_hits": 0,
                          "constant_abs_err": 0, "unanswered": 0})
    with open(os.path.join(bench, "configs", "msmarco-constant.json"),
              "w", encoding="utf-8") as f:
        json.dump(config, f)
    metric = _load(os.path.join(bench, "layer_metrics",
                                "plan.plan_build_ms.serial.json"))
    metric.update(name="plan.plan_build_ms.constant", workloads=[CELL])
    with open(os.path.join(bench, "layer_metrics",
                           f"{metric['name']}.json"), "w",
              encoding="utf-8") as f:
        json.dump(metric, f)
    manifest = _load(os.path.join(root, "BENCHMARK.json"))
    manifest["configs"].append({
        "name": "msmarco-constant", "source": "a test's own",
        "file": "benchmark/configs/msmarco-constant.json",
        "reduced": ["docs"], "why": "a second reference, added as files"})
    manifest["workloads"].append({
        "name": CELL, "config": "msmarco-constant",
        "traffic": "serial-match-top10", "chips": 1,
        "why": "the files-only rehearsal"})
    for m in manifest["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    manifest["per_layer"].append(
        {k: metric[k] for k in ("name", "unit", "better", "source", "layer",
                                "moves", "workloads")})
    with open(os.path.join(root, "BENCHMARK.json"), "w",
              encoding="utf-8") as f:
        json.dump(manifest, f, indent=1)
    return root


def _rehearse(root, *more):
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "tests",
                                      "rehearsal.py"),
         "--workload", CELL, "--seed", str(2**31 + 27), "--seconds", "3",
         *more], capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "REHEARSE_DOCS": "1500"})
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1]) if proc.returncode == 0 and lines else None
    return proc, last, [json.loads(l) for l in lines[:-1]
                        if l.startswith('{"phase"')]


def test_nothing_of_the_harness_was_edited(tree):
    git = ["git", "-C", tree]
    stat = subprocess.run(
        git + ["diff", "--stat", "--", "benchmark/harness",
               "benchmark/readers", "benchmark/run.py"],
        capture_output=True, text=True, check=True).stdout
    assert stat == ""
    added = subprocess.run(
        git + ["status", "--porcelain", "--untracked-files=all", "--",
               "BENCHMARK.json", "benchmark"],
        capture_output=True, text=True, check=True).stdout.splitlines()
    assert sorted(added) == sorted([
        " M BENCHMARK.json",
        "?? benchmark/configs/msmarco-constant.json",
        "?? benchmark/generators/constant_passages.py",
        "?? benchmark/layer_metrics/plan.plan_build_ms.constant.json",
        "?? benchmark/references/constant_score.py"])


def test_the_new_cell_is_correct_and_reports_its_own_metric(tree):
    proc, result, _ = _rehearse(tree, "--trace", "1")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert result["correct"] is True, result["compared"]
    assert result["compared"]["constant_abs_err"] == {"value": 0.0,
                                                      "limit": 0}
    assert "score_rel_err" not in result["compared"]
    assert list(result["metrics"]) == ["plan.plan_build_ms.constant"]
    assert result["attempted"] > 0


def test_its_control_is_not_correct(tree):
    proc, result, phases = _rehearse(tree, "--trace", "0",
                                     "--control", "plus_one")
    assert proc.returncode == 0, proc.stderr[-3000:]
    program = next(p for p in phases if p["phase"] == "program_compared")
    assert program["correct"] is True
    assert result["correct"] is False
    assert result["compared"]["constant_abs_err"]["value"] == 1.0
    assert "compared constant_abs_err: 1.0 limit 0" in proc.stderr


def test_a_control_the_reference_does_not_state_is_refused(tree):
    for control in ("bfloat16", "lost_ack"):  # another reference's; a
        proc, result, _ = _rehearse(tree, "--trace", "0",  # writers' own
                                    "--control", control)
        assert proc.returncode != 0 and result is None
        assert "states ['plus_one']" in proc.stderr
        assert '"correct"' not in proc.stdout
