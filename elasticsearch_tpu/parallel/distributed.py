"""Distributed query execution over a device mesh with ICI collectives.

The reference's cross-shard search is an RPC scatter-gather
(action/search/AbstractSearchAsyncAction + SearchTransportService,
"indices:data/read/search[phase/query]" fan-out, then
SearchPhaseController.sortDocs/TopDocs.merge on the coordinator). Here, for
shards living on one TPU slice, the whole scatter-gather is ONE compiled
program (SURVEY.md §5.7/§5.8):

  shard_map over mesh axis "shards":
    per-device: BM25 scatter-add scoring over the local shard's postings
                -> local lax.top_k
    collective: all_gather(topk) over ICI -> every device holds the global
                candidate set -> final lax.top_k  (the "TopDocs.merge")
    agg partials (counts/sums/histograms/HLL registers) -> psum over ICI

Shards are stacked to identical padded shapes (power-of-two buckets from
segment seal) so one program serves every shard — the mesh dimension is
just a leading axis.

DFS-stats mode (distributed IDF; search/dfs/DfsPhase.java:45): term df and
doc counts are psum'd across shards before weights are computed, giving
identical scores to a single-shard index — the reference needs an extra
network round-trip for this; here it is one collective in the same program.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from elasticsearch_tpu.ops.scoring import B, K1, bm25_idf


# ---------------------------------------------------------------------------
# Stacking shards to a uniform shape
# ---------------------------------------------------------------------------


def stack_shard_arrays(segments: List, n_devices: int,
                       slots: Optional[List[int]] = None
                       ) -> Dict[str, np.ndarray]:
    """Stack one segment per shard into mesh-ready arrays.

    All shards pad to the max bucketed shape. Returns host numpy arrays
    with a leading [n_devices] axis; segment i in row ``slots[i]`` (row i
    where None), the other rows dead (an all-false ``live1``).
    """
    if len(segments) > n_devices:
        raise ValueError(f"{len(segments)} shards > {n_devices} devices")
    nd_pad = max(s.nd_pad for s in segments)
    n_blocks = max(s.block_docs.shape[0] for s in segments)
    n_norm = max(s.norms.shape[0] for s in segments)
    blk = segments[0].block_docs.shape[1]

    block_docs = np.full((n_devices, n_blocks, blk), nd_pad, dtype=np.int32)
    block_tfs = np.zeros((n_devices, n_blocks, blk), dtype=np.float32)
    norms = np.ones((n_devices, n_norm, nd_pad + 1), dtype=np.float32)
    live1 = np.zeros((n_devices, nd_pad + 1), dtype=bool)
    for i, seg in zip(range(len(segments)) if slots is None else slots,
                      segments):
        bd = seg.block_docs.copy()
        bd[bd == seg.nd_pad] = nd_pad  # re-point sentinel to stacked pad
        block_docs[i, : bd.shape[0]] = bd
        block_tfs[i, : seg.block_tfs.shape[0]] = seg.block_tfs
        # norms columns beyond the segment's own nd_pad stay 1
        norms[i, : seg.norms.shape[0], : seg.norms.shape[1] - 1] = seg.norms[:, :-1]
        norms[i, :, nd_pad] = 1.0
        live1[i, : seg.live.shape[0]] = seg.live
    return {
        "block_docs": block_docs,
        "block_tfs": block_tfs,
        "norms": norms,
        "live1": live1,
        "nd_pad": nd_pad,
    }


def stack_query_arrays(segments: List, n_devices: int, field: str,
                       terms: List[str], qb_pad: int = 8) -> Dict[str, np.ndarray]:
    """Per-shard gather arrays for the same logical query (term ids differ
    per shard). Weights are left as *local* df/doc_count fractions when DFS
    mode is on — the kernel computes global idf after the psum."""
    qb = qb_pad
    per_shard = []
    for seg in segments:
        blocks, rows, avgdls, dfs = [], [], [], []
        term_slots = []
        for ti, t in enumerate(terms):
            tid = seg.term_id(field, t)
            if tid < 0:
                continue
            start = int(seg.term_block_start[tid])
            for bi in range(start, start + int(seg.term_block_count[tid])):
                blocks.append(bi)
                rows.append(seg.field_norm_idx.get(field, 0))
                avgdls.append(seg.field_avgdl(field))
                dfs.append(int(seg.term_doc_freq[tid]))
                term_slots.append(ti)
        per_shard.append((blocks, rows, avgdls, dfs, term_slots))
        qb = max(qb, len(blocks))
    n = 1
    while n < qb:
        n *= 2
    T = len(terms)
    out = {
        "q_blocks": np.zeros((n_devices, n), np.int32),
        "q_norm_rows": np.zeros((n_devices, n), np.int32),
        "q_avgdl": np.ones((n_devices, n), np.float32),
        "q_valid": np.zeros((n_devices, n), bool),
        "q_term_slot": np.zeros((n_devices, n), np.int32),
        # per-shard term stats for DFS psum: [n_devices, T]
        "term_df": np.zeros((n_devices, T), np.float32),
        "field_doc_count": np.zeros((n_devices, 1), np.float32),
        "field_sum_ttf": np.zeros((n_devices, 1), np.float32),
    }
    for i, seg in enumerate(segments):
        blocks, rows, avgdls, dfs, term_slots = per_shard[i]
        L = len(blocks)
        out["q_blocks"][i, :L] = blocks
        out["q_norm_rows"][i, :L] = rows
        out["q_avgdl"][i, :L] = avgdls
        out["q_valid"][i, :L] = True
        out["q_term_slot"][i, :L] = term_slots
        for ti, t in enumerate(terms):
            tid = seg.term_id(field, t)
            if tid >= 0:
                out["term_df"][i, ti] = float(seg.term_doc_freq[tid])
        out["field_doc_count"][i, 0] = float(
            seg.field_stats.get(field, {}).get("doc_count", 0)
        )
        out["field_sum_ttf"][i, 0] = float(
            seg.field_stats.get(field, {}).get("sum_ttf", 0)
        )
    return out


# ---------------------------------------------------------------------------
# The distributed program
# ---------------------------------------------------------------------------


def build_distributed_search(mesh: Mesh, k: int, with_histogram: bool = False,
                             n_hist_buckets: int = 32):
    """Compile the full distributed query-phase program.

    Returns fn(shard_arrays, query_arrays[, hist_arrays]) ->
      (top_scores [k], top_shard [k], top_doc [k], total_hits scalar
       [, hist_counts [n_hist_buckets]])
    — all replicated outputs (every device computes the same merge, the
    idiomatic way to keep results on-device for a following phase).
    """
    n_dev = mesh.devices.size

    def per_shard(block_docs, block_tfs, norms, live1, q_blocks, q_norm_rows,
                  q_avgdl, q_valid, q_term_slot, term_df, field_doc_count,
                  field_sum_ttf, *hist_args):
        # drop the leading per-device axis of size 1 from shard_map blocks
        block_docs = block_docs[0]
        block_tfs = block_tfs[0]
        norms = norms[0]
        live1 = live1[0]
        q_blocks, q_norm_rows = q_blocks[0], q_norm_rows[0]
        q_avgdl, q_valid, q_term_slot = q_avgdl[0], q_valid[0], q_term_slot[0]
        term_df, field_doc_count = term_df[0], field_doc_count[0]
        field_sum_ttf = field_sum_ttf[0]

        # ---- DFS phase: global term + collection stats via psum ----
        # (DfsPhase.termStatistics + CollectionStatistics: df, docCount and
        # sumTotalTermFreq must be corpus-global for score parity)
        g_df = jax.lax.psum(term_df, "shards")  # [T]
        g_doc_count = jax.lax.psum(field_doc_count, "shards")  # [1]
        g_sum_ttf = jax.lax.psum(field_sum_ttf, "shards")  # [1]
        idf = jnp.log(1.0 + (g_doc_count[0] - g_df + 0.5) / (g_df + 0.5))
        q_weights = jnp.where(q_valid, idf[q_term_slot], 0.0).astype(jnp.float32)
        g_avgdl = jnp.maximum(g_sum_ttf[0] / jnp.maximum(g_doc_count[0], 1.0), 1.0)

        # ---- local scoring (the per-shard hot loop) ----
        docs = block_docs[q_blocks]
        tfs = block_tfs[q_blocks]
        nd1_ = norms.shape[1]
        flat_idx = (q_norm_rows[:, None] * nd1_ + docs).ravel()
        doc_len = norms.ravel()[flat_idx].reshape(docs.shape)
        del q_avgdl  # local avgdl replaced by the DFS-global value
        denom = tfs + K1 * (1.0 - B + B * doc_len / g_avgdl)
        matched_blk = (tfs > 0.0) & q_valid[:, None]
        contrib = jnp.where(
            matched_blk, q_weights[:, None] * tfs * (K1 + 1.0) / denom, 0.0
        )
        nd1 = norms.shape[1]
        scores = jnp.zeros((nd1,), jnp.float32).at[docs].add(contrib)
        counts = jnp.zeros((nd1,), jnp.float32).at[docs].add(
            matched_blk.astype(jnp.float32)
        )
        matched = (counts > 0) & live1
        total_local = jnp.sum(matched.astype(jnp.int32))

        # ---- local top-k ----
        masked = jnp.where(matched, scores, -jnp.inf)
        kk = min(k, masked.shape[0])
        loc_scores, loc_docs = jax.lax.top_k(masked, kk)

        # ---- global merge over ICI (TopDocs.merge analog) ----
        my_shard = jax.lax.axis_index("shards")
        all_scores = jax.lax.all_gather(loc_scores, "shards").reshape(-1)
        all_docs = jax.lax.all_gather(loc_docs, "shards").reshape(-1)
        shard_ids = jnp.repeat(jnp.arange(n_dev, dtype=jnp.int32), kk)
        top_scores, top_idx = jax.lax.top_k(all_scores, kk)
        top_shard = shard_ids[top_idx]
        top_doc = all_docs[top_idx]
        total = jax.lax.psum(total_local, "shards")

        outs = [top_scores[None], top_shard[None], top_doc[None], total[None]]
        if with_histogram:
            flat_docs, flat_vals, interval, offset = hist_args
            flat_docs, flat_vals = flat_docs[0], flat_vals[0]
            interval, offset = interval[0], offset[0]
            bucket = jnp.floor(
                (flat_vals - offset[0]) / interval[0]
            ).astype(jnp.int32)
            ok = matched[flat_docs] & (bucket >= 0) & (bucket < n_hist_buckets)
            bucket = jnp.clip(bucket, 0, n_hist_buckets - 1)
            local_hist = jnp.zeros((n_hist_buckets,), jnp.int32).at[bucket].add(
                ok.astype(jnp.int32)
            )
            outs.append(jax.lax.psum(local_hist, "shards")[None])
        return tuple(outs)

    n_in = 12 + (4 if with_histogram else 0)
    in_specs = tuple([PS("shards")] * n_in)
    n_out = 4 + (1 if with_histogram else 0)
    # outputs replicated: shard_map requires every output to carry the mesh
    # axis or be produced identically; we gather+merge on every device and
    # emit with a leading 1-sized shards slice, then take index 0
    out_specs = tuple([PS("shards")] * n_out)

    mapped = shard_map(per_shard, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)

    @jax.jit
    def run(*args):
        outs = mapped(*args)
        # every device computed the same merged result; row 0 == row i
        return tuple(o[0] for o in outs)

    return run


class DistributedSearcher:
    """Host-side wrapper: stage stacked shards once, run compiled searches.

    This is the "one slice" data plane. The cross-slice path (multiple
    hosts) reuses the ShardQueryResult merge in search/service.py over DCN
    — mirroring the reference's coordinator merge.
    """

    def __init__(self, segments: List, mesh: Optional[Mesh] = None):
        from elasticsearch_tpu.parallel.mesh import shard_mesh

        self.mesh = mesh or shard_mesh()
        self.n_dev = self.mesh.devices.size
        self.segments = segments
        self.shard_arrays = stack_shard_arrays(segments, self.n_dev)
        self._programs: Dict[Tuple, object] = {}
        self._staged = None

    def _stage(self):
        if self._staged is None:
            sharding = NamedSharding(self.mesh, PS("shards"))
            self._staged = {
                name: jax.device_put(arr, sharding)
                for name, arr in self.shard_arrays.items()
                if name != "nd_pad"
            }
        return self._staged

    def search(self, field: str, terms: List[str], k: int = 10):
        q = stack_query_arrays(self.segments, self.n_dev, field, terms)
        qb_shape = q["q_blocks"].shape
        key = (k, qb_shape, False)
        if key not in self._programs:
            self._programs[key] = build_distributed_search(self.mesh, k)
        run = self._programs[key]
        staged = self._stage()
        sharding = NamedSharding(self.mesh, PS("shards"))
        args = [
            staged["block_docs"], staged["block_tfs"], staged["norms"],
            staged["live1"],
        ] + [jax.device_put(q[n], sharding) for n in (
            "q_blocks", "q_norm_rows", "q_avgdl", "q_valid", "q_term_slot",
            "term_df", "field_doc_count", "field_sum_ttf",
        )]
        top_scores, top_shard, top_doc, total = run(*args)
        return (
            np.asarray(top_scores), np.asarray(top_shard),
            np.asarray(top_doc), int(total),
        )
