"""Share of the train epochs' wall time the consumer spent blocked on the
device feed (``FeedStats.feed_wait_s``, as the program publishes it)."""


def read(run):
    f = run.facts
    if not f.get("train_epoch_wall_s"):
        return None
    return 100.0 * f["feed_wait_s"] / f["train_epoch_wall_s"]
