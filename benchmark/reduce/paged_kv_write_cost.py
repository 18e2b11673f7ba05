"""What a decode step's write into the page pool has to move: the new K and V
rows of the live (row, step) pairs, read once and written once a layer. The
engine counts the pairs on the dispatch's span (`kv_rows_written`); a row is
`n_kv_rows x row_width` values a leaf as the call's own pool leaf says
([L x P, n_kv_rows, page, row_width]: 8 heads of 128, or 4 lane rows of two
heads of 64). No operations. The kernel itself moves the aligned tile of 8
rows that holds each offset, there and back: that is its cost, not the
algorithm's, so a share of 1/16 is the tile's price and no fault."""

from __future__ import annotations


def paged_kv_write(kv_rows_written: int, steps: int, layers: float, pool_pages: int,
                   n_kv_rows: int, page_size: int, row_width: int,
                   bytes_per_elem: int = 2) -> dict:
    del steps, pool_pages, page_size  # the pool's size is no work
    row = 2 * n_kv_rows * row_width * bytes_per_elem  # K and V
    return {"ops": 0, "bytes": 2 * kv_rows_written * row * layers}  # in and out
