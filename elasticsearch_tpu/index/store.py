"""On-disk segment persistence + commit points.

Role model: ``Store`` (core/.../index/store/Store.java) + Lucene commits +
``MetaDataStateFormat`` atomic state files (gateway/MetaDataStateFormat).
A commit point is a JSON file listing the live segment set, max seqno and
tombstones, written atomically (tmp + rename). Segment payloads are
numpy ``.npz`` archives + JSON sidecars (term dictionary, sources).

Checksums: each segment directory carries a metadata file with per-array
SHA-256 digests, verified on load — the analog of Store's checksum
verification of Lucene segment files.

Corruption markers (ISSUE 16): the analog of the reference's
``Store.markStoreCorrupted`` — a detected ``CorruptIndexException``
writes a ``corrupted_*.json`` marker into the shard's store directory so
the bad copy can never be silently reused: every load path checks the
marker first and refuses. The marker is written once (the first detected
cause wins) and cleared only when a verified byte set replaces the
directory (peer-recovery file install wipes the directory; explicit
:meth:`Store.clear_corruption_markers` covers rebuild-in-place paths).
"""

from __future__ import annotations

import hashlib
import json
import os
import time
import uuid
from typing import Dict, List, Optional

import numpy as np

from elasticsearch_tpu.common.errors import ElasticsearchTpuException
from elasticsearch_tpu.index.segment import (
    StoredSources,
    GeoColumn,
    NestedContext,
    NumericColumn,
    OrdinalColumn,
    Segment,
    VectorColumn,
)


class CorruptIndexException(ElasticsearchTpuException):
    status_code = 500


MARKER_PREFIX = "corrupted_"


class Store:
    def __init__(self, directory: str):
        self.directory = directory
        os.makedirs(directory, exist_ok=True)

    # ------------------------------------------------------------------
    # corruption markers (Store.markStoreCorrupted parity)

    def corruption_markers(self) -> List[dict]:
        """Parsed ``corrupted_*.json`` markers, oldest first. An
        unreadable marker file still counts (an empty dict with its
        filename) — a torn marker must not unlock the copy."""
        out: List[dict] = []
        try:
            entries = sorted(os.listdir(self.directory))
        except FileNotFoundError:
            return out
        for entry in entries:
            if not (entry.startswith(MARKER_PREFIX)
                    and entry.endswith(".json")):
                continue
            p = os.path.join(self.directory, entry)
            if not os.path.isfile(p):
                continue
            try:
                with open(p, encoding="utf-8") as f:
                    marker = json.load(f)
            except (OSError, ValueError):
                marker = {}
            marker.setdefault("marker", entry)
            out.append(marker)
        return out

    def is_corrupted(self) -> bool:
        return bool(self.corruption_markers())

    def mark_corrupted(self, reason: str, site: str = "load") -> dict:
        """Write the corruption marker (once — the first cause wins) and
        return it. Idempotent: re-marking an already-marked store keeps
        the original marker so the first detected cause survives."""
        existing = self.corruption_markers()
        if existing:
            return existing[0]
        marker = {
            "marker": f"{MARKER_PREFIX}{uuid.uuid4().hex[:16]}.json",
            "reason": str(reason),
            "site": site,
            "timestamp_ms": int(time.time() * 1000),
        }
        os.makedirs(self.directory, exist_ok=True)
        tmp = os.path.join(self.directory, marker["marker"] + ".tmp")
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(marker, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, os.path.join(self.directory, marker["marker"]))
        return marker

    def clear_corruption_markers(self) -> int:
        """Remove the markers — ONLY legal after a successful
        re-recovery installed a verified byte set (peer-recovery wipes
        the whole directory instead; this covers rebuild-in-place)."""
        cleared = 0
        for marker in self.corruption_markers():
            try:
                os.remove(os.path.join(self.directory, marker["marker"]))
                cleared += 1
            except OSError:
                pass
        return cleared

    def _check_not_corrupted(self) -> None:
        markers = self.corruption_markers()
        if markers:
            m = markers[0]
            raise CorruptIndexException(
                f"store [{self.directory}] is marked corrupted "
                f"[{m.get('marker')}]: {m.get('reason', 'unknown')} — "
                f"the copy must be re-recovered from a healthy copy, "
                f"never reloaded")

    # ------------------------------------------------------------------

    def _seg_dir(self, name: str) -> str:
        return os.path.join(self.directory, name)

    def _commit_path(self) -> str:
        return os.path.join(self.directory, "commit.json")

    def commit(self, segments: List[Segment], max_seqno: int,
               version_map: Optional[dict] = None,
               sync_id: Optional[str] = None) -> None:
        for seg in segments:
            if not os.path.exists(self._seg_dir(seg.name)):
                self.write_segment(seg)
            # always refresh the live (tombstone) masks — cheap
            self._refresh_live(seg, self._seg_dir(seg.name))
        commit = {
            "segments": [s.name for s in segments],
            "max_seq_no": int(max_seqno),
        }
        if sync_id is not None:
            # synced-flush marker (ISSUE 14, the reference's _flush/synced
            # sync_id commit user-data): a drained shutdown stamps it so a
            # warm restart can prove the commit covers every acked op —
            # recovery is then ops-free (zero translog replay)
            commit["sync_id"] = sync_id
        if version_map is not None:
            # persist what segments cannot re-derive: delete tombstones
            # (the seqno staleness guard consults them after restart) and
            # non-default primary terms (equal-seqno tie-breaks survive
            # recovery) — reference keeps both in Lucene soft-delete docs
            commit["tombstones"] = {
                doc_id: {"seq_no": int(e.seqno), "version": int(e.version),
                         "term": int(getattr(e, "term", 1))}
                for doc_id, e in version_map.items()
                if getattr(e, "deleted", False)
            }
            commit["doc_terms"] = {
                doc_id: int(e.term)
                for doc_id, e in version_map.items()
                if not getattr(e, "deleted", False)
                and getattr(e, "term", 1) != 1
            }
        tmp = self._commit_path() + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(commit, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self._commit_path())
        # garbage-collect segments dropped from the commit (post-merge)
        live_names = set(commit["segments"])
        for entry in os.listdir(self.directory):
            p = os.path.join(self.directory, entry)
            if os.path.isdir(p) and entry not in live_names:
                import shutil

                shutil.rmtree(p, ignore_errors=True)

    def _refresh_live(self, seg: Segment, d: str) -> None:
        np.save(os.path.join(d, "live.npy"), seg.live)
        for i, (_path, nctx) in enumerate(sorted(seg.nested.items())):
            self._refresh_live(nctx.segment, os.path.join(d, "nested", str(i)))

    def read_commit(self) -> Optional[dict]:
        try:
            with open(self._commit_path(), encoding="utf-8") as f:
                return json.load(f)
        except FileNotFoundError:
            return None

    def load_segments(self) -> List[Segment]:
        self._check_not_corrupted()
        commit = self.read_commit()
        if commit is None:
            return []
        return [self.read_segment(name) for name in commit["segments"]]

    # ------------------------------------------------------------------

    def write_segment(self, seg: Segment) -> None:
        self._write_segment_dir(seg, self._seg_dir(seg.name))

    def _write_segment_dir(self, seg: Segment, d: str) -> None:
        os.makedirs(d, exist_ok=True)
        arrays = {
            "term_block_start": seg.term_block_start,
            "term_block_count": seg.term_block_count,
            "term_doc_freq": seg.term_doc_freq,
            "block_docs": seg.block_docs,
            "block_tfs": seg.block_tfs,
            "norms": seg.norms,
            "seqnos": seg.seqnos,
            "versions": seg.versions,
        }
        for f, col in seg.numeric_columns.items():
            arrays[f"num.{f}.flat_values"] = col.flat_values
            arrays[f"num.{f}.flat_docs"] = col.flat_docs
            arrays[f"num.{f}.first_value"] = col.first_value
            arrays[f"num.{f}.min_value"] = col.min_value
            arrays[f"num.{f}.max_value"] = col.max_value
            arrays[f"num.{f}.exists"] = col.exists
        for f, col in seg.ordinal_columns.items():
            arrays[f"ord.{f}.flat_ords"] = col.flat_ords
            arrays[f"ord.{f}.flat_docs"] = col.flat_docs
            arrays[f"ord.{f}.first_ord"] = col.first_ord
            arrays[f"ord.{f}.exists"] = col.exists
        for f, col in seg.geo_columns.items():
            arrays[f"geo.{f}.lat"] = col.lat
            arrays[f"geo.{f}.lon"] = col.lon
            arrays[f"geo.{f}.flat_docs"] = col.flat_docs
            arrays[f"geo.{f}.first_lat"] = col.first_lat
            arrays[f"geo.{f}.first_lon"] = col.first_lon
            arrays[f"geo.{f}.exists"] = col.exists
        for f, col in seg.vector_columns.items():
            # the bf16-grid f32 host mirror persists as-is: reloading it
            # reproduces the exact device bf16 staging (docs/VECTOR.md)
            arrays[f"vec.{f}.vectors"] = col.vectors
            arrays[f"vec.{f}.exists"] = col.exists
        for f, mask in seg.exists_masks.items():
            arrays[f"exists.{f}"] = mask
        np.savez(os.path.join(d, "arrays.npz"), **arrays)
        np.save(os.path.join(d, "live.npy"), seg.live)

        meta = {
            "name": seg.name,
            "num_docs": seg.num_docs,
            "term_keys": seg.term_keys,
            "field_stats": seg.field_stats,
            "field_norm_idx": seg.field_norm_idx,
            "numeric_fields": {f: c.count for f, c in seg.numeric_columns.items()},
            "ordinal_fields": {
                f: {"terms": c.terms, "count": c.count}
                for f, c in seg.ordinal_columns.items()
            },
            "geo_fields": {f: c.count for f, c in seg.geo_columns.items()},
            "vector_fields": {
                f: {"dims": c.dims, "count": c.count}
                for f, c in seg.vector_columns.items()
            },
            "doc_ids": seg.doc_ids,
            "routings": seg.routings,
            # legacy _parent values (alongside routing; rebuilds the
            # IndexService.parents registry on recovery)
            "parents": seg.parents,
            # geo_shape sidecar: raw GeoJSON/WKT per doc (geometry rebuilt
            # lazily at query time)
            "shapes": {f: {str(doc): vals for doc, vals in per_doc.items()}
                       for f, per_doc in seg.shapes.items()},
        }
        with open(os.path.join(d, "meta.json"), "w", encoding="utf-8") as f:
            json.dump(meta, f)
        with open(os.path.join(d, "sources.jsonl"), "w", encoding="utf-8") as f:
            for text in seg.sources.texts():
                f.write(text + "\n")
        # positions sidecar (phrase queries): term_id -> {doc: [pos...]}
        with open(os.path.join(d, "positions.json"), "w", encoding="utf-8") as f:
            json.dump(
                {str(tid): {str(doc): pos.tolist() for doc, pos in per_doc.items()}
                 for tid, per_doc in seg.positions.items()},
                f,
            )
        # nested sub-segments: one sub-directory per path, recursively
        if seg.nested:
            nd = os.path.join(d, "nested")
            os.makedirs(nd, exist_ok=True)
            index = {}
            for i, (path, nctx) in enumerate(sorted(seg.nested.items())):
                sub = os.path.join(nd, str(i))
                self._write_segment_dir(nctx.segment, sub)
                np.save(os.path.join(sub, "parent_of.npy"), nctx.parent_of)
                np.save(os.path.join(sub, "offset_of.npy"), nctx.offset_of)
                # re-checksum: the join arrays must be covered too
                self._write_checksums(sub)
                index[str(i)] = path
            with open(os.path.join(nd, "index.json"), "w", encoding="utf-8") as f:
                json.dump(index, f)
        self._write_checksums(d)

    def _write_checksums(self, d: str) -> None:
        sums = {}
        for fn in ("arrays.npz", "meta.json", "sources.jsonl", "positions.json",
                   "parent_of.npy", "offset_of.npy",
                   os.path.join("nested", "index.json")):
            p = os.path.join(d, fn)
            if not os.path.exists(p):
                continue
            with open(p, "rb") as f:
                sums[fn] = hashlib.sha256(f.read()).hexdigest()
        with open(os.path.join(d, "checksums.json"), "w", encoding="utf-8") as f:
            json.dump(sums, f)

    def verify_checksums(self, name: str) -> None:
        self._verify_checksums_dir(self._seg_dir(name))

    def verify_segment(self, name: str) -> int:
        """Re-verify a sealed segment's checksums RECURSIVELY (nested
        sub-segments included) — the background scrubber's disk pass
        (ISSUE 16). Returns the number of bytes verified; raises
        :class:`CorruptIndexException` on the first mismatch."""
        return self._verify_segment_dir(self._seg_dir(name))

    def _verify_segment_dir(self, d: str) -> int:
        self._verify_checksums_dir(d)
        total = 0
        try:
            with open(os.path.join(d, "checksums.json"),
                      encoding="utf-8") as f:
                sums = json.load(f)
            for fn in sums:
                total += os.path.getsize(os.path.join(d, fn))
        except (OSError, ValueError):
            pass  # _verify_checksums_dir already vouched for the bytes
        nested = os.path.join(d, "nested")
        if os.path.isdir(nested):
            for entry in sorted(os.listdir(nested)):
                sub = os.path.join(nested, entry)
                if os.path.isdir(sub):
                    total += self._verify_segment_dir(sub)
        return total

    def _verify_checksums_dir(self, d: str) -> None:
        try:
            with open(os.path.join(d, "checksums.json"), encoding="utf-8") as f:
                sums = json.load(f)
        except FileNotFoundError:
            raise CorruptIndexException(
                f"segment [{os.path.basename(d)}] missing checksums"
            ) from None
        except ValueError:
            # torn/truncated checksums.json: unparseable manifest is
            # corruption, not a crash — same contract as a mismatch
            raise CorruptIndexException(
                f"segment [{os.path.basename(d)}] torn checksums"
            ) from None
        for fn, expected in sums.items():
            try:
                with open(os.path.join(d, fn), "rb") as f:
                    actual = hashlib.sha256(f.read()).hexdigest()
            except FileNotFoundError:
                raise CorruptIndexException(
                    f"segment file [{os.path.basename(d)}/{fn}] listed "
                    f"in checksums but missing on disk"
                ) from None
            if actual != expected:
                raise CorruptIndexException(
                    f"checksum failed for [{os.path.basename(d)}/{fn}] "
                    f"(stored={expected[:12]}, actual={actual[:12]})"
                )

    def read_segment(self, name: str) -> Segment:
        self._check_not_corrupted()
        return self._read_segment_dir(self._seg_dir(name))

    def _read_segment_dir(self, d: str) -> Segment:
        self._verify_checksums_dir(d)
        with open(os.path.join(d, "meta.json"), encoding="utf-8") as f:
            meta = json.load(f)
        data = np.load(os.path.join(d, "arrays.npz"))
        with open(os.path.join(d, "sources.jsonl"), encoding="utf-8") as f:
            # (a line a document, parsed when it is first read)
            sources = StoredSources(
                text for text in f.read().split("\n") if text.strip())
        with open(os.path.join(d, "positions.json"), encoding="utf-8") as f:
            pos_raw = json.load(f)
        positions = {
            int(tid): {int(doc): np.asarray(pos, dtype=np.int32)
                       for doc, pos in per_doc.items()}
            for tid, per_doc in pos_raw.items()
        }

        numeric_columns: Dict[str, NumericColumn] = {}
        for f_name, count in meta["numeric_fields"].items():
            numeric_columns[f_name] = NumericColumn(
                data[f"num.{f_name}.flat_values"],
                data[f"num.{f_name}.flat_docs"],
                data[f"num.{f_name}.first_value"],
                data[f"num.{f_name}.min_value"],
                data[f"num.{f_name}.max_value"],
                data[f"num.{f_name}.exists"],
                count,
            )
        ordinal_columns: Dict[str, OrdinalColumn] = {}
        for f_name, info in meta["ordinal_fields"].items():
            ordinal_columns[f_name] = OrdinalColumn(
                info["terms"],
                data[f"ord.{f_name}.flat_ords"],
                data[f"ord.{f_name}.flat_docs"],
                data[f"ord.{f_name}.first_ord"],
                data[f"ord.{f_name}.exists"],
                info["count"],
            )
        geo_columns: Dict[str, GeoColumn] = {}
        for f_name, count in meta["geo_fields"].items():
            geo_columns[f_name] = GeoColumn(
                data[f"geo.{f_name}.lat"],
                data[f"geo.{f_name}.lon"],
                data[f"geo.{f_name}.flat_docs"],
                data[f"geo.{f_name}.first_lat"],
                data[f"geo.{f_name}.first_lon"],
                data[f"geo.{f_name}.exists"],
                count,
            )
        exists_masks = {
            k[len("exists."):]: data[k] for k in data.files if k.startswith("exists.")
        }
        vector_columns: Dict[str, VectorColumn] = {}
        for f_name, info in (meta.get("vector_fields") or {}).items():
            vector_columns[f_name] = VectorColumn(
                data[f"vec.{f_name}.vectors"],
                data[f"vec.{f_name}.exists"],
                int(info["dims"]),
                int(info["count"]),
            )

        seg = Segment(
            name=meta["name"],
            num_docs=meta["num_docs"],
            doc_ids=meta["doc_ids"],
            sources=sources,
            routings=meta["routings"],
            seqnos=data["seqnos"],
            versions=data["versions"],
            term_keys=meta["term_keys"],
            term_block_start=data["term_block_start"],
            term_block_count=data["term_block_count"],
            term_doc_freq=data["term_doc_freq"],
            block_docs=data["block_docs"],
            block_tfs=data["block_tfs"],
            field_stats=meta["field_stats"],
            field_norm_idx=meta["field_norm_idx"],
            norms=data["norms"],
            numeric_columns=numeric_columns,
            ordinal_columns=ordinal_columns,
            geo_columns=geo_columns,
            exists_masks=exists_masks,
            positions=positions,
            shapes={f: {int(doc): vals for doc, vals in per_doc.items()}
                    for f, per_doc in (meta.get("shapes") or {}).items()},
            parents=meta.get("parents"),
            vector_columns=vector_columns,
        )
        live_path = os.path.join(d, "live.npy")
        if os.path.exists(live_path):
            seg.live = np.load(live_path)
        nested_index = os.path.join(d, "nested", "index.json")
        if os.path.exists(nested_index):
            with open(nested_index, encoding="utf-8") as f:
                index = json.load(f)
            for i, path in index.items():
                sub = os.path.join(d, "nested", i)
                seg.nested[path] = NestedContext(
                    segment=self._read_segment_dir(sub),
                    parent_of=np.load(os.path.join(sub, "parent_of.npy")),
                    offset_of=np.load(os.path.join(sub, "offset_of.npy")),
                )
        return seg
