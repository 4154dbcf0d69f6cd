"""Lanes a round of the compacted loop carries: the sum of
``lane_slots`` over the sum of ``rounds`` of the window's fits
(``backend.last_round_stats``; the round loop's own rounds — the
finalize pass books its rounds apart and no lane slots). Every pass of
a solver step reads the shared operands once per round, so this is how
many fits one read serves. ``None`` where the program books no lane
slots (its stats lack the key or hold ``None``): the metric is left
out."""


def read(ctx):
    booked = [f["stats"] for f in ctx["fits"]
              if f["stats"] and f["stats"].get("lane_slots") is not None]
    rounds = sum(s.get("rounds") or 0 for s in booked)
    if not rounds:
        return None
    return sum(s["lane_slots"] for s in booked) / rounds
