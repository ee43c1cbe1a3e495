"""The one traffic generator: reads a mix from ``traffic/<name>.json``.

A mix is data:

    loop       "closed": each client sends its next query when the last
               one has returned
    clients    clients of a closed loop (one today)
    strategy   the execution strategy each request asks for
    order      "round_robin": the queries in the listed order, repeated
    queries    SSB query names, as ``reference.QUERIES`` names them

The seed draws the data, not the sequence: every seed sends the same
queries in the same order, so runs differ only in the tables.
"""
from __future__ import annotations

import itertools
from typing import Iterator

from chipbench import reference

LOOPS = ("closed",)
ORDERS = ("round_robin",)


def validate(mix: dict) -> dict:
    if mix["loop"] not in LOOPS or mix["order"] not in ORDERS:
        raise ValueError(f"unsupported loop/order {mix['loop']}/"
                         f"{mix['order']}; known {LOOPS}/{ORDERS}")
    if int(mix["clients"]) != 1:
        raise ValueError("closed loops of one client only")
    unknown = set(mix["queries"]) - set(reference.QUERIES)
    if unknown or not mix["queries"]:
        raise ValueError(f"unknown queries {sorted(unknown)}")
    return mix


def sequence(mix: dict) -> Iterator[str]:
    """The query names one client sends, without end."""
    return itertools.cycle(validate(mix)["queries"])


def distinct(mix: dict) -> list:
    """Each query the mix sends, once, in first-sent order."""
    return list(dict.fromkeys(validate(mix)["queries"]))
