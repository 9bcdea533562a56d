"""Host time of the AFT publish of the new weight step (one WORKFLOW-scope
transaction over the weight shards)."""


def read(run):
    return run.publish["publish_s"] if run.publish else None
