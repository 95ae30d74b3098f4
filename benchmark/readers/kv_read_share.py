"""What `paged_attention` reads of the block tables it is given: over
the window's iterations, the pages that the fed rows' lengths cover
(`kv_pages_read`: what a read that follows each row's own length
touches) over rows x table width (`kv_pages_table`: what a gather of
every table entry touches), in %. Both are counted by the engine from
the `start` / `n_valid` arrays it feeds, into its iteration records.

`before_slice` as in `iteration_record`. None where the program's
records lack the two fields (the parent of the PR that added them), or
none lies in the window.
"""
from benchmark.readers import iteration_record


def read(ctx, before_slice=False):
    recs = [r for r in iteration_record.records(ctx, before_slice)
            if "kv_pages_table" in r]
    table = sum(r["kv_pages_table"] for r in recs)
    return 100.0 * sum(r["kv_pages_read"] for r in recs) / table \
        if table else None
