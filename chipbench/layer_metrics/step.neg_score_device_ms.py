"""Device time a step of the chunked scoring against negatives (ms): the ops
under ``ps.kge_score`` + ``ps.kge_score_grad`` inside ``ps.compute``
(``models/kge.py``: a chunk's ``(50, 100) x (100, 100)`` product a side with
its softmax, and the two transposed products of the backward pass with the
join of the gradient rows).  The operators' element-wise products
(``ps.kge_operator``) and their AdaGrad (``ps.kge_operator_update``) are left
out.  A program without those scopes (every other logic, the parent) reports
nothing."""
from chipbench import program_trace

SCOPES = ("ps.kge_score", "ps.kge_score_grad")


def read(ctx):
    return program_trace.scope_ms(ctx, *SCOPES)
