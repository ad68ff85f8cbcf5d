"""Share of the pages the buffer pool handed out in the window that it
already held: the ``hits`` over the ``pages`` of the program's
``pool.fetch`` spans."""
from bench import spans


def read(run):
    clipped = spans.window(run)
    if clipped is None:
        return None
    pages = spans.count(clipped, "pool.fetch", "pages")
    if pages <= 0:
        return None
    return 100.0 * spans.count(clipped, "pool.fetch", "hits") / pages
