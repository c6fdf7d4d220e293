"""Seconds from process start to the start of the window: loading, weight
generation, the int8 tier's preparation, compiles or cache fetches, and in a
backlog the filling of every slot."""
from __future__ import annotations


def read(rec):
    return rec["setup_s"]
