"""The one traffic generator: a traffic file's parameters -> the jobs.

A traffic file (``bench/traffic/<name>.json``) is data only:

  driver       "session": one client, one ``gson.Session`` at a time, in
               a closed loop (the next job starts when the last returns)
  rounds       a list of rounds, each a list of jobs ``{"surface": s,
               "seed": n}``; the seeds are fixed in the file, so every
               run plays the same point clouds
  window_end   "round": a round that has started when ``--seconds`` runs
               out is finished, so the window always holds whole rounds
  check        {"supersteps": k} how many supersteps the comparison with
               the reference takes, drawn from the run's seed; "all":
               every distinct superstep the window ran
  trace_seconds how long the traced run records, from the window's start
               (it stops at the next job or superstep boundary)

Every run plays the rounds in order, each round's jobs in an order drawn
from ``--seed``, and starts again from the first round when they run
out: every seed gets the same set of jobs, in another order.
"""
from __future__ import annotations

import random
from dataclasses import dataclass
from typing import Iterator

DRIVERS = ("session",)
WINDOW_ENDS = ("round",)


@dataclass(frozen=True)
class Job:
    surface: str
    seed: int
    round: int          # index of the round played (counts up)
    first: bool         # first job of its round


def validate(traffic: dict) -> None:
    """Raise ValueError on a traffic file the generator cannot play."""
    if traffic.get("driver") not in DRIVERS:
        raise ValueError(f"driver must be one of {DRIVERS}")
    if traffic.get("window_end") not in WINDOW_ENDS:
        raise ValueError(f"window_end must be one of {WINDOW_ENDS}")
    rounds = traffic.get("rounds")
    if not rounds or not all(rounds):
        raise ValueError("rounds must be a non-empty list of non-empty "
                         "lists of jobs")
    for job in (j for r in rounds for j in r):
        if not isinstance(job.get("surface"), str):
            raise ValueError(f"job without a surface: {job}")
        seed = job.get("seed")
        if not (isinstance(seed, int) and seed >= 0):
            raise ValueError(f"job seed must be an int >= 0: {job}")
    k = traffic.get("check", {}).get("supersteps")
    if k != "all" and not (isinstance(k, int) and k >= 1):
        raise ValueError("check.supersteps must be 'all' or an int >= 1")
    if float(traffic.get("trace_seconds", 0)) <= 0:
        raise ValueError("trace_seconds must be > 0")


def surfaces(traffic: dict) -> list[str]:
    """The distinct surfaces the traffic uses, in first-use order."""
    return list(dict.fromkeys(j["surface"] for r in traffic["rounds"]
                              for j in r))


def jobs(traffic: dict, seed: int) -> Iterator[Job]:
    """The endless job sequence of one run."""
    rng = random.Random(seed)
    rounds = traffic["rounds"]
    played = 0
    while True:
        for r in rounds:
            order = list(r)
            rng.shuffle(order)
            for i, job in enumerate(order):
                yield Job(job["surface"], job["seed"], played, i == 0)
            played += 1
