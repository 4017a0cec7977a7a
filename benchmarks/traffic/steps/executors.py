"""For each executor of a granted gang: POST /predicates, then bind.  Each
has to be given the reserved node the reference hands that executor.  A
refused gang sends no executors."""

from traffic import answered_nodes

CHECKS = {"executor_answers_wrong": 0}


def run(s):
    if s.node is None:
        return
    for pod in s.created[1:]:
        with s.annotate("client.filter_executor"):
            answer = s.client.filter(pod)
        placed = s.answered("executor", answer)
        if placed is not None:
            with s.annotate("client.bind"):
                s.client.bind(pod, placed)


def compare(rec, c):
    found = rec.answers.get("executor", [])
    if c.grant is None:
        c.wrong["executor_answers_wrong"] += len(found)  # none was due
        return
    c.wrong["answers_missing"] += max(rec.gang.executors - len(found), 0)
    for answer in found:
        c.compared += 1
        want = c.reference.filter_executor(rec.gang, c.node_names)
        if answered_nodes(answer[2]) != ([want] if want else []):
            c.wrong["executor_answers_wrong"] += 1
