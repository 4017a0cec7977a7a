"""kube-scheduler binds the driver to the granted node."""


def run(s):
    if s.node is not None:
        with s.annotate("client.bind"):
            s.client.bind(s.created[0], s.node)
