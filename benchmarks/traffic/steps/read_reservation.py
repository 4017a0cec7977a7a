"""Read the acknowledged reservation back from the scheduler: the driver's
node and every executor slot's node, in slot order."""

CHECKS = {"reservations_wrong": 0}


def run(s):
    with s.annotate("client.readback"):
        s.rec.read["reservation"] = s.client.reservation(s.gang.app_id)


def compare(rec, c):
    c.compared += 1
    want = (c.grant.driver_node, c.grant.executor_nodes) if c.grant is not None else None
    if rec.read.get("reservation") != want:
        c.wrong["reservations_wrong"] += 1
