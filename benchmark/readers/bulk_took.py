"""Sum of ``took`` of the window's ``_bulk`` answers over the documents
they acknowledged, ms per document."""


def read(ctx, params):
    bulks = [r for r in ctx["records"] if r["kind"] == "bulk"
             and r["status"] == 200 and r.get("created")]
    docs = sum(r["created"] for r in bulks)
    return sum(r["took"] for r in bulks) / docs if docs else None
