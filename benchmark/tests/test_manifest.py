"""The manifest check: sound on the committed files, and it catches the
fault that refused PR 23."""

import copy
import os
import subprocess
import sys

from harness import manifest_check

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _manifest():
    return manifest_check.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def test_committed_manifest_is_sound():
    assert manifest_check.check(ROOT) == []


def test_command_line_check_needs_no_jax():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--check-manifest"], capture_output=True, text=True, timeout=60,
        env={**os.environ, "JAX_PLATFORMS": "no_such_platform"})
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "manifest: sound"


def test_layer_metric_in_a_cell_without_what_it_moves_is_refused():
    m = _manifest()
    metric = next(x for x in m["per_layer"]
                  if x["name"] == "frontdoor.client_side_ms.serial")
    # PR 23's refusal: a cell that does not report search_p50_ms
    m["workloads"].append(dict(m["workloads"][0], name="http-logs-append",
                               traffic="append2x100-dash2qps"))
    m["end_to_end"].append({"name": "ingest_docs_per_s", "unit": "docs/s",
                            "better": "higher", "bound": 0.25,
                            "source": "host_clock",
                            "workloads": ["http-logs-append"]})
    metric["workloads"].append("http-logs-append")
    faults = manifest_check.check(ROOT, m)
    assert any("http-logs-append" in f and "search_p50_ms" in f
               for f in faults), faults


def test_layer_metric_without_a_list_of_cells_is_refused():
    m = _manifest()
    del m["per_layer"][0]["workloads"]
    assert any("no workloads list" in f for f in manifest_check.check(ROOT, m))


def _faults_after(change):
    m = copy.deepcopy(_manifest())
    change(m)
    return manifest_check.check(ROOT, m)


def test_other_rules_of_the_contract():
    assert _faults_after(lambda m: m["workloads"][0].update(chips=2))
    assert _faults_after(lambda m: m["end_to_end"][0].update(unit="per second"))
    assert _faults_after(lambda m: m["end_to_end"][0].update(bound=0.5))
    assert _faults_after(lambda m: m["workloads"][0].update(traffic="absent"))
    assert _faults_after(lambda m: m["per_layer"][0].update(why="x"))
    assert _faults_after(lambda m: m["configs"][0].update(source="x" * 201))
    assert _faults_after(lambda m: m.update(run_seconds=52))
    assert _faults_after(lambda m: m["workloads"].extend(
        dict(m["workloads"][0], name=f"four{i}", chips=4,
             traffic="closed16-match-top10") for i in range(2)))
    assert _faults_after(
        lambda m: m["end_to_end"].append(dict(m["end_to_end"][0],
                                              name="never_reported",
                                              workloads=[])))
