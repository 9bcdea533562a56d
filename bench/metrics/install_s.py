"""From the publish's commit to the last replica's weight swap: the
refresher's AFT read of the shards and each ``install_weights``."""


def read(run):
    return run.publish["install_s"] if run.publish else None
