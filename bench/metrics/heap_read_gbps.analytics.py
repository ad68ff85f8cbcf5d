"""Rate of the heap file's page reads: the ``bytes`` of the program's
``heap.read`` spans in the window over their time, in GB/s. Near
``feed_gbps.analytics``, the reads set the feed's pace; far above it, the
pool's own work does."""
from bench import spans


def read(run):
    return spans.gbps(run, "heap.read")
