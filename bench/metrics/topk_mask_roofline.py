"""Roofline share of the TopK mask kernel (compression kernels,
``kernels/topk.py``, ``_mask_kernel``): the least time the chip needs for
the kernel's bytes over the kernel's summed device time in the trace.

The kernel reads each leaf of each node padded to whole (256 x 128) tiles
and writes it back (2 * padded elements * itemsize bytes per leaf and
node, plus a 4-byte threshold), once per gossip step. Its arithmetic, an
abs, a compare and a select per element, is far below the chip's peak, so
the bytes bound it: least time = bytes / HBM bandwidth (819 GB/s,
``bench/peaks.json``)."""

KERNEL = "_mask_kernel"
TILE = 256 * 128


def kernel_bytes(leaves, nodes):
    """Bytes one mask pass over every leaf of every node moves;
    ``leaves`` is a list of (elements per node, itemsize)."""
    total = 0
    for size, itemsize in leaves:
        padded = -(-size // TILE) * TILE
        total += nodes * (2 * padded * itemsize + 4)
    return total


def read(summary):
    seconds = summary.get("kernels", {}).get(KERNEL)
    if not seconds:
        return None
    passes = summary["rounds"] * summary["traffic"]["tau2"]
    moved = passes * kernel_bytes(summary["leaves"],
                                  summary["traffic"]["nodes"])
    least = moved / summary["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
