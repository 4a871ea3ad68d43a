"""Process start to the first request of the window: start-up, the
native build if absent, the tables from the seed, the reference's
answers, the session, and the warm-up executions with their compiles."""
UNIT = "s"


def reduce(window):
    return window["setup_s"]
