"""Seconds of TrainStep's eager discovery pass: the program's jit.build span,
as the driver summed it during set-up."""


def read(trace, spans, facts):
    return facts.get("discovery_s")
