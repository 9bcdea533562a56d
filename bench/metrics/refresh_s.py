"""Staleness: from the publish call of the new weight step to the moment
every replica serves it."""


def read(run):
    return run.publish["refresh_s"] if run.publish else None
