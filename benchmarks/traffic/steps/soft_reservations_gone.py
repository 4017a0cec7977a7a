"""After the application has retired: the soft store holds no entry of
it, and as many applications in all as the reference holds (none, with
one client)."""

CHECKS = {"soft_reservations_left": 0}


def run(s):
    with s.annotate("client.readback_soft"):
        s.rec.read["soft_left"] = (
            s.objects.soft_reservations(s.client, s.gang) is not None,
            s.objects.soft_applications(s.client),
        )


def compare(rec, c):
    c.compared += 1
    if rec.read.get("soft_left") != (False, c.reference.soft_applications()):
        c.wrong["soft_reservations_left"] += 1
