"""Tokens the served model processed inside the window, over the window:
the real prompt tokens of every prefill chunk, and every generated token
(one per live slot of each decode call, one per finished prompt), counted
from the calls the engines made in the window, whether or not their
request has finished by its end."""


def read(run):
    toks = 0
    for c in run.calls:
        if c[1] == "prefill":
            toks += c[2] + int(c[4])
        else:
            toks += c[2]
    return toks / run.seconds
