"""Share of the HBM roofline the resident store's install program reached
in the traced span: least bytes over measured time.

The program (ops/slab.py `install_fn`, rewritten in PR 38: keyed by the
source's shape and told the trim width at run time) compacts an encode
product's plane rows into the flat page image and scatters its pages into a
sub-slab.  Least bytes: every page it lands is read once and written once,
2 x `pagestore.install_page_bytes` (PR 38; a lower bound: the program also
reads the source's pad columns and zero-fills the image, so the share can
only read low, never over 100).  Time: the XLA Modules events named
`jit__install`.  A program without the counter, or a span without an
install, reports nothing."""

from benchmarks import peaks, trace_reduce


def install_min_bytes(page_bytes_installed: float) -> float:
    return 2.0 * page_bytes_installed


def read(ctx):
    red = ctx.get("trace")
    if not red or red["window_s"] <= 0 or not red["devices"]:
        return None
    nbytes = ctx["trace_counters"].get("pagestore.install_page_bytes", 0)
    kernel_s = sum(trace_reduce.time_by_name(
        red["modules"], red["t0"], red["t1"], r"^jit__install").values())
    if kernel_s <= 0 or nbytes <= 0:
        return None
    return peaks.roofline_share(install_min_bytes(nbytes), kernel_s,
                                ctx["device_kind"])
