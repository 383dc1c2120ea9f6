"""One run of one cell: set-up, warm-up, the measured window, the
comparison with the reference, the result line."""

from __future__ import annotations

import importlib
import json
import os
import shutil
import subprocess
import sys
import threading
import time

import numpy as np

import references
from harness import manifest_check, server, trace, work
from harness.comparison import Comparison, compare_between
from harness.corpus import rng_for

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
READ_BACK_SEARCHES = 3  # of each operation, sent again after the window


def say(phase: str, **fields) -> None:
    """An earlier line of standard output: what the run found on its
    way, never read by the driver."""
    print(json.dumps({"phase": phase, **fields}), flush=True)


def load_cell(manifest: dict, workload: str):
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise server.HarnessFailure(f"no cell {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = manifest_check.load_json(os.path.join(ROOT, entry["file"]))
    traffic = manifest_check.load_json(
        os.path.join(BENCH, "traffic", f"{cell['traffic']}.json"))
    return cell, config, traffic


def resolve(value, config: dict):
    """``"config.<key>"`` in a traffic file reads the configuration."""
    if isinstance(value, str) and value.startswith("config."):
        return config[value[len("config."):]]
    return value


# ----------------------------------------------------------------------
# Request sequences: what each client of the load generator replays
# ----------------------------------------------------------------------


def write_sequences(outdir: str, traffic: dict, config: dict, dataset,
                    seed: int, profile: bool):
    """One file of requests per client. Returns (client specs for the
    generator, {request id: ref} for the comparison, writer groups)."""
    ops = dataset.operations()
    specs, refs, writers = [], {}, set()
    for g, group in enumerate(traffic["clients"]):
        count = int(resolve(group["count"], config))
        for c in range(count):
            rows = []
            if group["operations"] == ["append"]:
                writers.add(group["name"])
                per = int(resolve(group["docs_per_request"], config))
                base, pool = dataset.n_docs, dataset.append_pool
                for k in range(pool // (per * count)):
                    lo = base + (k * count + c) * per
                    rows.append({
                        "method": "POST",
                        "path": f"/{config['index']}/_bulk",
                        "ctype": "application/x-ndjson", "kind": "bulk",
                        "id": ["append", lo, lo + per],
                        "body": dataset.bulk_body(lo, lo + per)})
            else:
                lists = [ops[name] for name in group["operations"]]
                rng = rng_for(seed, 1000 + 100 * g + c)
                orders = [rng.permutation(len(l)) for l in lists]
                for i in range(max(len(l) for l in lists) * len(lists)):
                    which = i % len(lists)
                    j = int(orders[which][(i // len(lists))
                                          % len(lists[which])])
                    req = lists[which][j]
                    body = dict(req["body"])
                    if profile:
                        body["profile"] = True
                    rid = [group["operations"][which], j]
                    refs[tuple(rid)] = req["ref"]
                    rows.append({
                        "method": req["method"], "path": req["path"],
                        "ctype": "application/json", "kind": "search",
                        "id": rid, "body": json.dumps(body)})
            path = os.path.join(outdir, f"seq-{group['name']}-{c}.jsonl")
            with open(path, "w", encoding="utf-8") as f:
                for row in rows:
                    f.write(json.dumps(row) + "\n")
            specs.append({"group": group["name"], "client": c,
                          "loop": group["loop"],
                          "rate_per_s": group.get("rate_per_s"),
                          "max_rate_per_s": group.get("max_rate_per_s"),
                          "wrap": group["operations"] != ["append"],
                          "file": path})
    return specs, refs, writers


class Generator:
    """The load generator's process and its line protocol."""

    def __init__(self, outdir: str, port: int, specs: list):
        self.outdir = outdir
        spec = os.path.join(outdir, "loadgen.json")
        with open(spec, "w", encoding="utf-8") as f:
            json.dump({"port": port, "outdir": outdir, "clients": specs}, f)
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(BENCH, "harness", "loadgen.py"),
             spec], stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        self._reply()

    def _reply(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            raise server.HarnessFailure("the load generator ended early")
        reply = json.loads(line)
        if "error" in reply:
            raise server.HarnessFailure(f"load generator: {reply['error']}")
        return reply

    def command(self, *words) -> dict:
        self.proc.stdin.write(" ".join(str(w) for w in words) + "\n")
        self.proc.stdin.flush()
        reply = self._reply()
        if "tag" in reply:
            with open(os.path.join(self.outdir, f"{reply['tag']}.json"),
                      encoding="utf-8") as f:
                reply.update(json.load(f))
        return reply

    def close(self) -> None:
        if self.proc.poll() is None:
            try:
                self.proc.stdin.write("quit\n")
                self.proc.stdin.flush()
                self.proc.wait(timeout=30)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
                self.proc.wait()


# ----------------------------------------------------------------------
# Warm-up
# ----------------------------------------------------------------------


def _compiled(served, index: str) -> int:
    c = served.search_stats(index)["compile"]
    return (c["query_path_first_compile_total"] + c["programs_warmed_total"]
            + c["variants_recorded"])


def warm_up(gen: Generator, served, index: str, traffic: dict,
            writers: set) -> list:
    """The cell's own traffic until no program has compiled for a few
    seconds; bursts of the widths a batcher could form; and last the
    cell's own traffic again. Returns the records of every phase."""
    w = traffic["warmup"]
    records = []
    readers = [g["name"] for g in traffic["clients"]
               if g["name"] not in writers]
    if writers and readers and w.get("readers_only_seconds"):
        # the base as it was loaded: answers with a known reference
        got = gen.command("run", w["readers_only_seconds"], "warm-readers",
                          *readers)
        for r in got["records"]:
            r["before_append"] = True
        records += got["records"]
    t0, chunk, last = time.monotonic(), 0, _compiled(served, index)
    sent = 0
    while True:
        got = gen.command("run", w["settle_seconds"], f"warm-{chunk}")
        records += got["records"]
        chunk += 1
        sent += len(got["records"])
        now, elapsed = _compiled(served, index), time.monotonic() - t0
        if w.get("fixed_chunks"):  # a cell that writes: the same amount
            if chunk >= w["fixed_chunks"]:  # written before every window
                break
            continue
        if (now == last and elapsed >= w["min_seconds"]
                and sent >= w.get("min_requests", 0)) \
                or elapsed >= w["max_seconds"]:
            break
        last = now
    for n in w.get("bursts", []):
        for rep in range(2):
            records += gen.command("burst", n, f"burst-{n}-{rep}")["records"]
    if w.get("bursts"):
        records += gen.command("run", w["settle_seconds"],
                               "warm-last")["records"]
    say("warmup", seconds=time.monotonic() - t0, chunks=chunk, requests=sent,
        compiled=_compiled(served, index))
    return records


# ----------------------------------------------------------------------
# The traced interval
# ----------------------------------------------------------------------


class Tracer:
    """A ``jax.profiler`` trace of a few seconds in the middle of the
    window, taken by the process that holds the chip."""

    def __init__(self, outdir: str, seconds: float, traffic: dict):
        self.dir = os.path.join(outdir, "trace")
        wanted = traffic.get("trace_seconds", trace.rules()["trace_seconds"])
        self.length = min(wanted, seconds * 0.8)
        self.delay = (seconds - self.length) / 2.0
        self.marks = []  # time.monotonic_ns() of each mark left in the trace
        self.error = None
        self.thread = threading.Thread(target=self._run)

    def _run(self) -> None:
        import jax

        try:
            time.sleep(self.delay)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=options)
            try:
                self._mark()
                time.sleep(self.length)
                self._mark()
            finally:
                jax.profiler.stop_trace()
        except Exception as e:  # the traced run reports it and goes on
            self.error = repr(e)

    def _mark(self) -> None:
        """An annotation in the trace whose time on the clients' clock
        is known: what carries the device's window onto that clock."""
        from jax.profiler import TraceAnnotation

        self.marks.append(time.monotonic_ns())
        with TraceAnnotation(trace.MARK):
            time.sleep(0.001)

    def reduce(self, keep=None):
        self.thread.join()
        if self.error:
            say("trace", error=self.error)
            return None
        lines = trace.read_xplane(trace.find_xplane(self.dir))
        out = trace.reduce(lines, trace.rules())
        out["clock_offset_ns"], disagree = trace.clock_offset_ns(
            lines, trace.rules(), self.marks)
        if keep:  # a recording for tests/test_trace.py: a few events a line
            with open(keep, "w", encoding="utf-8") as f:
                json.dump([dict(l, events=l["events"][:200])
                           for l in lines], f)
        say("trace", inventory=trace.inventory(lines)[:40],
            busy_s=out["busy_s"], window_s=out["window_s"],
            devices=out["devices"], device_window_ns=out["device_window_ns"],
            clock_offset_ns=out["clock_offset_ns"],
            clock_marks_disagree_ns=disagree)
        return out


# ----------------------------------------------------------------------
# Metrics
# ----------------------------------------------------------------------


def _ok(r: dict) -> bool:
    if r["status"] != 200:
        return False
    if r["kind"] == "bulk":
        return r["errors"] is False and r["created"] == r["items"]
    return not r["timed_out"] and not r["shards_failed"]


def end_to_end(records: list, start: float, seconds: float,
               setup_s: float) -> dict:
    """Every end-to-end metric the window can give, over all its
    requests and all its time; the cell reports those it lists."""
    end = start + seconds
    search = [r for r in records if r["kind"] == "search"
              and r["status"] == 200]
    out = {"setup_s": setup_s}
    if search:
        lat = np.asarray([(r["done"] - r["due"]) * 1e3 for r in search])
        out["search_p50_ms"] = float(np.percentile(lat, 50))
        out["search_p95_ms"] = float(np.percentile(lat, 95))
        out["search_qps"] = sum(1 for r in search if r["done"] <= end) / seconds
    bulks = [r for r in records if r["kind"] == "bulk" and r["status"] == 200]
    if bulks:
        out["ingest_docs_per_s"] = sum(
            r["created"] for r in bulks if r["done"] <= end) / seconds
    return out


def per_layer(manifest: dict, workload: str, ctx: dict) -> dict:
    out = {}
    for m in manifest["per_layer"]:
        if workload not in m["workloads"]:
            continue
        held = manifest_check.load_json(
            os.path.join(BENCH, "layer_metrics", f"{m['name']}.json"))
        reader = importlib.import_module(f"readers.{held['reader']}")
        value = reader.read(ctx, held.get("params", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ----------------------------------------------------------------------
# The comparison with the reference
# ----------------------------------------------------------------------


def _answer(r: dict) -> dict:
    return {"total": r["total"], "ids": [int(i) for i in r["ids"]],
            "scores": r["scores"],
            "aggs": {name: {int(k): int(c) for k, c in buckets if c}
                     for name, buckets in r.get("aggs", {}).items()}}


def _sample_ids(records: list, refs: dict, weigh, cap: int, seed: int):
    """The distinct requests to compare: all, or a sample drawn from the
    seed that keeps the heaviest by ``weigh(ref)``."""
    ids = sorted({tuple(r["id"]) for r in records})
    if len(ids) <= cap:
        return set(ids)
    heavy = max(ids, key=lambda i: weigh(refs[i]))
    pick = rng_for(seed, 7).choice(len(ids), cap - 1, replace=False)
    return {ids[int(j)] for j in pick} | {heavy}


def compare_searches(cmp, records, refs, reference, weigh, seed, cap,
                     control=None):
    """Every answered search of ``records`` against ``reference``, the
    configuration's own on the view the answers were given on.
    ``control`` puts the reference's answer at that precision in the
    program's place, once a request."""
    chosen = _sample_ids(records, refs, weigh, cap, seed)
    by_id = {}
    for r in records:
        if tuple(r["id"]) in chosen:
            by_id.setdefault(tuple(r["id"]), []).append(r)
    for rid, recs in by_id.items():
        for r in recs[:1] if control else recs:
            reference.compare(cmp, f"{rid[0]}[{rid[1]}]", _answer(r),
                              refs[rid], control=control)


def compare_appends(cmp, config, dataset, base, records, refs, after):
    """The write path: every bulk acknowledged whole; after the final
    refresh the count and a seeded sample read back by id; answers under
    ingest between those of ``base``, the reference on the base's view,
    and those of the reference on the final state's."""
    bulks = [r for r in records if r["kind"] == "bulk"]
    present = np.zeros(dataset.n_docs + dataset.append_pool, bool)
    present[: dataset.n_docs] = True
    unsure = 0
    for r in bulks:
        _, lo, hi = r["id"]
        if _ok(r) and r["items"] == hi - lo:
            present[lo:hi] = True
        elif r["status"] == 200:
            cmp.note("bulk_items_wrong", 1, f"bulk {lo}-{hi}")
        else:
            unsure += hi - lo  # no answer: the documents may be there
    cmp.compared += len(bulks)
    n_hi = int(np.flatnonzero(present).max()) + 1
    present = present[:n_hi]
    acked = int(present.sum())
    count = after["count"]
    cmp.note("count_abs_diff",
             max(0, acked - count, count - acked - unsure), "final count")
    missing = wrong = 0
    for doc_id, source in after["readback"].items():
        if source is None:
            missing += 1
        elif source != dataset.source(int(doc_id)):
            wrong += 1
    cmp.note("readback_missing", missing, "read-back by id")
    cmp.note("readback_wrong_fields", wrong, "read-back by id")
    # answers: exact after the final refresh, bounded under ingest
    final = references.build(config, dataset.view(n_hi))
    window = [r for r in records if r["kind"] == "search" and _ok(r)
              and not r.get("before_append")]
    for r in window + after["searches"]:
        rid = tuple(r["id"])
        ref, what = refs[rid], f"{rid[0]}[{rid[1]}]"
        lo_m = base.matched(ref)
        hi_m = final.matched(ref) & present
        answer = _answer(r)
        if r.get("after_refresh"):
            final.compare(cmp, what, answer, ref, among=hi_m)
            continue
        cmp.compared += 1
        compare_between(
            cmp, "total_out_of_range", what, {0: answer["total"]},
            {0: int(lo_m.sum())}, {0: int(hi_m.sum())})
        lo_b, hi_b = base.buckets(ref, lo_m), final.buckets(ref, hi_m)
        for name in hi_b:
            compare_between(
                cmp, "bucket_out_of_range", f"{what}.{name}",
                answer["aggs"].get(name, {}), lo_b[name], hi_b[name])


def read_back(served, config, dataset, records, refs, seed) -> dict:
    """After the window: a final _refresh, the count, a seeded sample of
    the acknowledged documents by id, and each dashboard request once."""
    index, http = config["index"], served.http
    http.request("POST", f"/{index}/_refresh")
    count = http.request("POST", f"/{index}/_search",
                         {"size": 0, "query": {"match_all": {}}})
    acked = np.concatenate([
        np.arange(r["id"][1], r["id"][2]) for r in records
        if r["kind"] == "bulk" and _ok(r)] or [np.zeros(0, np.int64)])
    n = min(len(acked), config["check"]["readback_ids"])
    sample = rng_for(seed, 8).choice(acked, n, replace=False) if n else []
    found = {}
    for lo in range(0, n, 500):
        ids = [str(int(i)) for i in sample[lo: lo + 500]]
        resp = http.request("POST", f"/{index}/_search", {
            "size": len(ids), "query": {"ids": {"values": ids}}})
        got = {h["_id"]: h["_source"] for h in resp["hits"]["hits"]}
        found.update({i: got.get(i) for i in ids})
    searches = []
    seen = set()  # the last requests sent, a few of each operation
    for r in reversed(records):
        rid = tuple(r["id"])
        if r["kind"] == "search" and sum(
                1 for s in seen if s[0] == rid[0]) < READ_BACK_SEARCHES:
            seen.add(rid)
    ops = dataset.operations()
    for rid in sorted(seen):
        req = ops[rid[0]][rid[1]]
        resp = http.request(req["method"], req["path"], req["body"])
        hits = resp["hits"]
        searches.append({
            "id": list(rid), "kind": "search", "after_refresh": True,
            "total": hits["total"],
            "ids": [h["_id"] for h in hits["hits"]],
            "scores": [h["_score"] for h in hits["hits"]],
            "aggs": {name: [[b["key"], b["doc_count"]]
                            for b in agg.get("buckets", [])]
                     for name, agg in resp.get("aggregations", {}).items()}})
    return {"count": count["hits"]["total"], "readback": found,
            "searches": searches}


# ----------------------------------------------------------------------
# The run
# ----------------------------------------------------------------------


def run(args, manifest: dict, t_process: float) -> dict:
    """Everything between the device check and the result line. Returns
    the result; raises ``HarnessFailure`` where there is none to give."""
    cell, config, traffic = load_cell(manifest, args.workload)
    chips = cell["chips"]
    controls = set(references.named_by(config).controls)
    if any(g["operations"] == ["append"] for g in traffic["clients"]):
        controls.add("lost_ack")  # the harness's own, where a cell writes
    if args.control and args.control not in controls:
        raise server.HarnessFailure(
            f"--control {args.control!r}: the cell's reference "
            f"{config['reference']!r} states {sorted(controls)}")
    devices = server.require_tpu(chips)
    t_jax = time.monotonic()
    server.build_native()
    cache = server.configure_compile_cache()
    n_shards = config["shards_per_chip"] * chips
    generator = importlib.import_module(f"generators.{config['generator']}")
    dataset = generator.Dataset(config, args.seed, n_shards)
    t_corpus = time.monotonic()
    base = server.ensure_base(config, dataset, args.seed, chips, n_shards, say)
    outdir = os.path.join(server.CACHE, f"run-{args.workload}")
    shutil.rmtree(outdir, ignore_errors=True)
    os.makedirs(outdir)
    gen = served = None
    try:
        specs, refs, writers = write_sequences(
            outdir, traffic, config, dataset, args.seed, bool(args.trace))
        # every run works on a copy and deletes it at exit: the node
        # writes beside its store (translog, the registry of compiled
        # variants), and a stored base must stay as it was built
        data_path = os.path.join(outdir, "data")
        shutil.copytree(base, data_path,
                        ignore=shutil.ignore_patterns("READY"))
        t_base = time.monotonic()
        served = server.Served(data_path)
        index = config["index"]
        live = served.translog_durabilities(index)
        want = config["settings"]["index.translog.durability"]
        if live != [want] * n_shards:
            raise server.HarnessFailure(
                f"live translogs report {live}, the deployment is {want}")
        t_open = time.monotonic()
        gen = Generator(outdir, served.port, specs)
        warm = warm_up(gen, served, index, traffic, writers)
        before = served.search_stats(index)
        tracer = Tracer(outdir, args.seconds, traffic) if args.trace else None
        if tracer:
            tracer.thread.start()
        window = gen.command("run", args.seconds, "window")
        setup_s = window["start"] - t_process
        after = served.search_stats(index)
        device = server.device_block(devices)
        traced = tracer.reduce(args.keep_trace) if tracer else None
        records = window["records"]
        say("setup", jax_start_s=t_jax - t_process,
            corpus_s=t_corpus - t_jax, build_or_find_base_s=t_base - t_corpus,
            open_s=t_open - t_base, warmup_s=window["start"] - t_open,
            compile_cache=os.path.relpath(cache, ROOT) if cache else None,
            durability=live)
        compiles = (after["compile"]["query_path_first_compile_total"]
                    - before["compile"]["query_path_first_compile_total"])
        decisions = {k: v - before["phases"]["decisions"].get(k, 0)
                     for k, v in after["phases"]["decisions"].items()}
        planes = {}
        for r in records:
            if r["kind"] == "search" and r["status"] == 200:
                planes[r["plane"]] = planes.get(r["plane"], 0) + 1
        lates = [(r["sent"] - r["due"]) * 1e3 for r in records]
        say("window", requests=len(records), planes=planes,
            expect_plane=config["expect_plane"], decisions=decisions,
            compiles_in_window=compiles, exhausted=window["exhausted"],
            generator_late_ms_mean=float(np.mean(lates)) if lates else None,
            generator_late_ms_max=float(np.max(lates)) if lates else None,
            drained_s=window["closed"] - window["start"] - args.seconds)
        if window["exhausted"]:
            raise server.HarnessFailure(
                f"request sequences ran out: {window['exhausted']}")
        readback = None
        if writers:
            readback = read_back(served, config, dataset, warm + records,
                                 refs, args.seed)
    finally:
        if gen:
            gen.close()
        if served:
            served.close()
    # the window has closed, the peak is read, the program's state freed
    t0 = time.monotonic()
    reference = references.build(config, dataset.view(dataset.n_docs))
    peaks = work.peaks_for(device["kind"])
    cap = config["check"]["max_distinct_requests"]

    def weigh(ref):
        return work.least_seconds(reference.work(ref), peaks, chips)

    def compare(control):
        cmp = Comparison(config["limits"])
        cmp.note("unanswered",
                 sum(1 for r in records if r["status"] == -1), "window")
        # a cell that writes is exact on the base, before its first append
        exact = ([r for r in warm if r.get("before_append")] if writers
                 else records)
        compare_searches(
            cmp, [r for r in exact if r["kind"] == "search" and _ok(r)],
            refs, reference, weigh, args.seed, cap,
            control=None if control == "lost_ack" else control)
        if writers:
            compare_appends(cmp, config, dataset, reference, warm + records,
                            refs, readback)
            if control == "lost_ack":
                cmp.note("count_abs_diff", 1, "control: one acknowledged "
                         "document taken out of the count")
        return cmp

    cmp = compare(None)
    if args.control:  # both readings of one run: the program's, then
        say("program_compared", correct=cmp.correct(),  # the control's
            numbers=cmp.numbers())
        cmp = compare(args.control)
    numbers = cmp.numbers()
    say("reference", seconds=time.monotonic() - t0, compared=cmp.compared,
        where=cmp.where)
    shutil.rmtree(outdir, ignore_errors=True)
    metrics = end_to_end(records, window["start"], args.seconds, setup_s)
    listed = {m["name"]: m for m in manifest["end_to_end"]
              if args.workload in manifest_check.reporting_cells(
                  m, manifest["workloads"])}
    if args.trace:
        ctx = {"records": records, "refs": refs, "stats_before": before,
               "stats_after": after, "trace": traced,
               "reference": reference, "peaks": peaks, "chips": chips,
               "window": {"start": window["start"], "seconds": args.seconds}}
        out_metrics = per_layer(manifest, args.workload, ctx)
    else:
        out_metrics = {name: {"value": metrics[name], "unit": m["unit"]}
                       for name, m in listed.items() if name in metrics}
    result = {"correct": cmp.correct(), "attempted": len(records),
              "failed": sum(1 for r in records if not _ok(r)),
              "metrics": out_metrics, "device": device}
    if traced and traced["devices"]:
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = traced["window_s"]
        result["breakdown"] = {"device_ops": traced["device_ops"],
                               "idle_gaps": traced["idle_gaps"]}
    result["compared"] = numbers
    return result
