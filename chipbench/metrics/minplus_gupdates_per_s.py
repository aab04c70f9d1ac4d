"""Min-plus updates per second of the rebuild's kernels, in 10^9.

Each rebuild is one Floyd-Warshall over the (capacity x capacity) matrix:
capacity^3 updates ``d[i,j] = min(d[i,j], d[i,k] + d[k,j])``, whatever
implements it.  Divided by the device time of the min-plus kernels inside
the rebuild's programs in the trace (``chipbench/tracereduce.py``).
"""


def read(ctx):
    t = ctx.trace
    if not t or t["rebuild_modules"] == 0 or t["rebuild_kernel_s"] <= 0:
        return None
    return float(ctx.capacity) ** 3 * t["rebuild_modules"] \
        / t["rebuild_kernel_s"] / 1e9
