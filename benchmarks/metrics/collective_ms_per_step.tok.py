"""Time of the chips' collectives (all-reduce, reduce-scatter, all-gather,
all-to-all, collective-permute on the lines ``XLA Ops`` and ``Async XLA Ops``,
start to done, their union on a chip, a mean over the chips) over the steps
of the traced window: ``trace_reduce.reduce``'s ``collective_s``. Nothing to
read without a device trace, and left out where no collective ran."""


def read(record):
    t = record["trace"]
    if t is None or not t.get("steps") or not t.get("collective_s"):
        return None
    return 1e3 * t["collective_s"] / t["steps"]
