"""The driver pod appears: pending, behind the backlog."""


def run(s):
    with s.annotate("client.create"):
        s.pods = s.objects.pods(s.gang)
        s.created = [s.client.create(s.pods[0])]
