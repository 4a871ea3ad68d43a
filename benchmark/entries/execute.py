"""``Session.execute``: one chip, rows on the host when it returns (the
download is the sync)."""


def run(sess, df, config):
    return sess.execute(df.plan).to_rows()


def faults(metrics, config):
    """What ``Session.last_metrics`` must say after a request."""
    level = metrics.get("fault.degradeLevel")
    return [] if level == 0 else [f"fault.degradeLevel is {level}"]
