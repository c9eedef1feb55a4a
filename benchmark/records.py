"""Append-only JSON lines shared by every incarnation of the worker.

A killed worker cannot write at exit, so what the parent needs is on disk
before the kill. No JAX here.
"""

import json
import time


class Record:
    def __init__(self, path: str, **ident):
        self._path = path
        self._ident = ident

    def write(self, event: str, **fields):
        line = json.dumps(
            {"event": event, "t": time.time(), **self._ident, **fields},
            default=str,
        )
        with open(self._path, "a") as f:
            f.write(line + "\n")


def read(path: str) -> list:
    try:
        with open(path) as f:
            return [json.loads(line) for line in f if line.strip()]
    except OSError:
        return []


def of(records: list, event: str, **match) -> list:
    return [
        r for r in records if r["event"] == event
        and all(r.get(k) == v for k, v in match.items())
    ]
