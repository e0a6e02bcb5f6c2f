"""The same operation (``op``) again and again; one runs in set-up as the
warm-up."""
import itertools


def plan(mix):
    op = {"op": mix["op"]}
    return [dict(op)], (dict(op) for _ in itertools.count())
