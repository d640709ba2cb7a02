"""The packed int4 dequant-matmul kernel's share of its roofline, in %:
the least time the chip needs for the kernel calls of the traced window
(required FLOPs and bytes from shapes, real rows only) over the summed
device time of the kernel's trace events."""
import devtrace
import roofline

#: the kernel's device events: the Pallas call of the packed kernel
KERNEL = r"_packed_kernel|dequant_matmul_packed"


def read(run):
    if not run.trace:
        return None
    kernel_s = devtrace.seconds_matching(run.trace["op_s"], KERNEL)
    calls = run.traced_dispatches()
    if kernel_s <= 0 or not calls:
        return None
    _, _, least = roofline.packed_matmul_work(run.spec, calls, run.peak)
    return 100.0 * least / kernel_s
