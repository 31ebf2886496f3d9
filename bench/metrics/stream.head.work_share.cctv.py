"""Share of a head without exit's weak-classifier work that the stream
step's masked head ran: the ``head_work`` of the program's ``stream.poll``
spans (evaluations of (8, 128) tiles, stage by stage, that the head ran)
over their ``head_dense`` (every tile of the levels the step evaluated
through every stage), between the first and the last counted
completion."""

from bench import stream_counts


def read(ctx: dict):
    polls = stream_counts.poll_spans(ctx)
    if not polls:
        return None
    dense = sum(s.attrs["head_dense"] for s in polls)
    if not dense:
        return None
    return 100 * sum(s.attrs["head_work"] for s in polls) / dense
