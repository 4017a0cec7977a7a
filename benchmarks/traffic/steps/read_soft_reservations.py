"""Read the application's soft reservations back from the scheduler:
which executor beyond min is held on which node.  Outside the timed
Filter.  A mix may ask more than once for a gang (after the ramp, after
a loss): the readings are kept, and compared, in order."""

CHECKS = {"soft_reservations_wrong": 0}


def run(s):
    with s.annotate("client.readback_soft"):
        held = s.objects.soft_reservations(s.client, s.gang)
    s.rec.read.setdefault("soft_reservations", []).append(held)


def compare(rec, c):
    # this verb's n-th turn for the gang compares its n-th reading
    turns = vars(c).setdefault("soft_turns", {})
    turn = turns.get(rec.gang.app_id, 0)
    turns[rec.gang.app_id] = turn + 1
    c.compared += 1
    readings = rec.read.get("soft_reservations", [])
    if turn >= len(readings):
        c.wrong["answers_missing"] += 1
    elif readings[turn] != c.reference.soft_reservations(rec.gang):
        c.wrong["soft_reservations_wrong"] += 1
