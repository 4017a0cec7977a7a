"""Read the durable copy back: the ResourceReservation object that the
scheduler's write-back creates in the API server after it has answered.
Outside the timed Filter; a copy that comes late is waited for (a minute
at the most), one that never comes or says another node is wrong.  A
refused gang must have none."""

CHECKS = {"api_reservations_wrong": 0}


def run(s):
    with s.annotate("client.readback_api"):
        s.rec.read["api_reservation"] = s.client.api_reservation(s.gang.app_id, due=s.node is not None)


def compare(rec, c):
    c.compared += 1
    want = (c.grant.driver_node, c.grant.executor_nodes) if c.grant is not None else None
    if rec.read.get("api_reservation") != want:
        c.wrong["api_reservations_wrong"] += 1
