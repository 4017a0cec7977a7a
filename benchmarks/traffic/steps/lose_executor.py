"""An executor that held a hard slot dies and Spark replaces it: the pod
bound to slot 1 is deleted, the scheduler sees it go, the replacement pod
appears, is Filtered and bound.  At that Filter the scheduler compacts: a
soft-reserved executor of the application takes over the freed slot.

The replacement's Filter is timed, under a kind of its own
(``replacement_executor``): ``executors.compare`` consumes every answer
of kind ``executor`` before this verb's turn in the replay.  It counts
in ``pods_per_s``, not in the executor percentiles.  A refused gang
loses nothing."""

from traffic import answered_nodes

CHECKS = {"replacement_answers_wrong": 0}
KIND = "replacement_executor"
SLOT = 1


def run(s):
    if s.node is None:
        return
    with s.annotate("client.lose_executor"):
        lost = s.objects.slot_executor(s.client, s.gang, SLOT)
        s.rec.read["lost_executor"] = lost
        if lost is None:
            return
        victim = next(p for p in s.created[1:] if s.objects.executor_index(s.gang, p.name) == lost)
        s.created.remove(victim)  # retire deletes what is left
        # an application that may hold executors beyond its min is queued for compaction by the death
        s.objects.delete_and_wait(s.client, victim, compaction_due=s.gang.executors > s.gang.min_executors)
        pod = s.client.create(s.objects.replacement(s.gang))
        s.created.append(pod)
    with s.annotate("client.filter_executor"):
        answer = s.client.filter(pod)
    placed = s.answered(KIND, answer)
    if placed is not None:
        with s.annotate("client.bind"):
            s.client.bind(pod, placed)


def compare(rec, c):
    found = rec.answers.get(KIND, [])
    if c.grant is None:
        c.wrong["replacement_answers_wrong"] += len(found)  # none was due
        return
    lost, want = c.reference.lose_executor(rec.gang, c.node_names, SLOT)
    if not found:
        c.wrong["answers_missing"] += 1
        return
    c.compared += 1
    if rec.read.get("lost_executor") != lost or answered_nodes(found[0][2]) != ([want] if want else []):
        c.wrong["replacement_answers_wrong"] += 1
