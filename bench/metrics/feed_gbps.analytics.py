"""Rate at which the buffer pool hands out pages while it works: the
``bytes`` of the program's ``pool.fetch`` spans in the window over their
time (hit lookups, disk reads of the misses, frame copies), in GB/s."""
from bench import spans


def read(run):
    return spans.gbps(run, "pool.fetch")
