"""POST /predicates for the driver: one node, or a refusal where the
reference refuses."""

from traffic import answered_nodes

CHECKS = {"driver_answers_wrong": 0}


def run(s):
    with s.annotate("client.filter_driver"):
        answer = s.client.filter(s.created[0])
        s.rec.read["lane"] = s.client.queue_lane()
    s.node = s.answered("driver", answer)


def compare(rec, c):
    c.grant = c.reference.filter_driver(rec.gang)
    found = rec.answers.get("driver")
    if not found:
        c.wrong["answers_missing"] += 1
        return
    c.compared += 1
    want = [c.grant.driver_node] if c.grant is not None else []
    if answered_nodes(found[0][2]) != want:
        c.wrong["driver_answers_wrong"] += 1
