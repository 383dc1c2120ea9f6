"""chip_smoke.py rehearsed on the CPU: interpret-mode kernels, the
platform assertion patched here (the script itself has no CPU option).

What the chip run proves end to end — plane, numpy oracle, last line —
must already hold here at a tiny size, so a chip call is not spent on a
wrong path, argument or check.
"""

import json
import os

import jax
import pytest

import chip_smoke
from elasticsearch_tpu.common import compile_cache


@pytest.fixture()
def smoke(monkeypatch, tmp_path):
    monkeypatch.setenv("ES_TPU_PALLAS", "interpret")
    monkeypatch.setattr(chip_smoke, "require_tpu",
                        lambda chips: jax.devices())
    # xdist workers share the checkout: no rebuild under their feet
    monkeypatch.setattr(chip_smoke, "build_native", lambda: None)
    cache_dir = str(tmp_path / "jax_cache")
    monkeypatch.setattr(compile_cache, "checkout_cache_dir",
                        lambda: cache_dir)
    yield cache_dir
    compile_cache.configure_compile_cache(None)


def test_one_chip_phase_passes_and_ends_with_the_contract_line(
        smoke, capfd):
    rc = chip_smoke.main(["--docs", "2000", "--queries", "3",
                          "--warm", "3"])
    out = capfd.readouterr().out.strip().splitlines()
    assert rc == 0, out
    dev = jax.devices()[0]
    assert json.loads(out[-1]) == {"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}
    phases = {p["phase"]: p for p in map(json.loads, out[:-1])}
    assert phases["ingest"]["docs"] == 2000
    assert phases["planes"]["decisions"] == {"mesh_pallas.served": 8}
    assert phases["match"]["max_rel_err"] <= chip_smoke.SCORE_RTOL
    assert phases["warm"]["compiles_in_window"] == 0
    assert phases["staging"]["staged_bytes_total"] > 0
    if not os.environ.get(compile_cache.CACHE_DIR_ENV):
        assert phases["setup"]["compile_cache"] == smoke
        assert os.listdir(smoke)  # the script's compiles were cached


def test_one_shard_index_leaves_the_plane_and_fails(smoke, monkeypatch,
                                                    capfd):
    # trap 1: a one-shard index is answered by the host plane with
    # status 200 — only the plane assertion tells
    monkeypatch.setattr(chip_smoke, "SHARDS_PER_CHIP", 1)
    rc = chip_smoke.main(["--docs", "300", "--queries", "1",
                          "--warm", "0"])
    captured = capfd.readouterr()
    assert rc != 0
    assert "not mesh_pallas" in captured.err
    assert '"ok"' not in captured.out


def test_no_tpu_is_a_failure_without_a_result_line(capfd):
    assert chip_smoke.main([]) != 0
    captured = capfd.readouterr()
    assert "no TPU" in captured.err
    assert captured.out == ""
