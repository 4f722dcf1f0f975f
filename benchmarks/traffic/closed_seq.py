"""Closed loop, one statement at a time: each session is one client who
sends a statement, waits for the answer and sends the next, cycling over
every class, the order within each cycle shuffled from the seed.

Parameters: ``sessions`` (clients, default 1), ``lane`` (optional WLM
lane sent with each request)."""

import random


def schedule(params, classes, rng):
    def session(rng):
        order = list(classes)
        while True:
            rng.shuffle(order)
            for cls in order:
                yield {"due_s": None, "sends": [cls]}
    return [session(random.Random(rng.getrandbits(64)))
            for _ in range(int(params.get("sessions", 1)))]
