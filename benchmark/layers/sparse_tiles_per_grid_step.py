"""sparse_tiles_per_grid_step (layer: kernels) - the selected [block, D]
tiles of K (and as many of V) that ONE grid step of the sparse layers'
kernel `sparse_decode_attention` staged, at the mean over the step
dispatches of the measured window: `kv_blocks_live` over `kv_grid_steps`
summed over the program's `decode/fetch` spans of `phase=step` (both
counted on the host from the slots' lengths by the kernel's own rule:
`DecodeSession._sparse_stream`; a running slot's K/V head takes
ceil(min(sparse_topk, blocks in sight) / T) steps a sparse layer a trip, T
the kernel's `pallas_kernels.sparse_tiles_per_step`).  T while every stream
holds more than `sparse_topk` blocks, less for a short one whose last step
is part empty; a grid step's fixed cost is paid once for that many tiles.
A program whose spans carry no `kv_grid_steps` (one tile a step, the
kernel before PR 49) gives no reading."""

from benchmark import spans as sp


def read(spans, trace, run):
    tiles = steps = 0
    for s in sp.named(spans, "decode/fetch", run["window"]):
        a = s["attrs"]
        if a.get("phase") == "step" and a.get("kv_grid_steps"):
            tiles += int(a["kv_blocks_live"])
            steps += int(a["kv_grid_steps"])
    return tiles / float(steps) if steps else None
