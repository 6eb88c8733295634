"""decode_kv_stream_share (layer: kernels) - the share of the slot table's
K/V blocks that `decode_attention` staged, in percent: 100 x
`kv_blocks_live` / `kv_blocks_total` over the program's `decode/fetch`
spans of `phase=step` in the measured window (blocks of the kernel's
`block_kv` positions, over a dispatch's trips, slots and attention
layers).  A program whose kernel streams every block of every slot stamps
neither attribute, and each of its fetches counts as 100 (weighed by its
`trips`, 1 where it stamps none).  Down to the live rows' share of the
table, rounded up to a block a slot, where the stream stops at a slot's
length (PERF.md section 6, PR 32).  No such span, no reading."""

from benchmark import spans as sp


def read(spans, trace, run):
    staged = trips = 0.0
    for s in sp.named(spans, "decode/fetch", run["window"]):
        a = s["attrs"]
        if a.get("phase") != "step":
            continue
        n, whole = a.get("trips", 1), a.get("kv_blocks_total")
        # a dispatch's whole rows are its trips times a constant of the
        # table, so shares weighed by trips are blocks over blocks
        staged += n * (a["kv_blocks_live"] / float(whole) if whole else 1.0)
        trips += n
    return 100.0 * staged / trips if trips else None
