"""The driver and all its executor pods appear."""


def run(s):
    with s.annotate("client.create"):
        s.pods = s.objects.pods(s.gang)
        s.created = [s.client.create(p) for p in s.pods]
