"""``Session.execute`` on a plan the session has not seen: one chip,
rows on the host when it returns (the download is the sync).

A request arrives as a new plan, as one that came in as SQL text would:
the logical plan is the cell's, the object is new, so the session plans
it again and every scan, filter and broadcast build below it runs in
this request.  Sent again as the same object (``entries/execute.py``) a
plan finds its physical tree in the session's plan cache and with it
the broadcast relations an earlier request built
(``exec/broadcast.py``): the tables on a join's build side are then
read once a process, not once a request."""
import copy

from benchmark.harness import load_module

#: what ``Session.last_metrics`` must say after a request: as for a
#: request sent as the same object
faults = load_module("entries", "execute").faults


def run(sess, df, config):
    return sess.execute(copy.copy(df.plan)).to_rows()
