"""Mean number of live slots per decode call in the window, counted from
the slot positions the engine passes to its decode program."""


def read(run):
    live = [c[2] for c in run.calls if c[1] == "decode"]
    return sum(live) / len(live) if live else None
