"""Share of the window the train loop waited for a batch: the feed's own
``DeviceFeed.stats()["consumer_wait"]`` over the window's length."""


def read(run):
    if "feed_consumer_wait_s" not in run.counters:
        return None
    return 100.0 * run.counters["feed_consumer_wait_s"] / run.window_s
