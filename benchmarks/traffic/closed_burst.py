"""Closed loop of bursts: each session is one dashboard whose refresh
fires every class at once (one thread each, released together), and
whose next refresh starts when the last tile has answered; no think
time. Tile order within a refresh is shuffled from the seed.

Parameters: ``sessions`` (dashboards, default 1), ``lane`` (optional WLM
lane sent with each request)."""

import random


def schedule(params, classes, rng):
    def session(rng):
        order = list(classes)
        while True:
            rng.shuffle(order)
            yield {"due_s": None, "sends": list(order)}
    return [session(random.Random(rng.getrandbits(64)))
            for _ in range(int(params.get("sessions", 1)))]
