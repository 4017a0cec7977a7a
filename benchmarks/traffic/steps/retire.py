"""The application ends: its pods are deleted and the reservation goes."""


def run(s):
    with s.annotate("client.retire"):
        s.client.retire(s.created, s.gang.app_id)


def compare(rec, c):
    c.reference.retire(rec.gang)
