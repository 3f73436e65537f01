"""repro.serving — the continuous-batching projection engine (async
submit/poll, DESIGN.md §5) and LM prefill/decode serve steps."""
from .engine import (DeadlineExceededError, ProjectionEngine,  # noqa: F401
                     QueueFullError, ServingError, Ticket,
                     UnknownTicketError)
from .lm import generate, make_decode_step, make_prefill  # noqa: F401
