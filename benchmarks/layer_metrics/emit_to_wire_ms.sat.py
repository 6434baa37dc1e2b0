"""Mean time of a token from the tick thread's push (_token_out) to the return of the event loop's writer.write, saturated cells: the wait in the loop's queue and for the interpreter lock, json.dumps and the write (health()['stream']: emit_to_wire_us over stream_tokens), in the profiled, untraced stretch before the trace."""
from benchmarks.harness import readers_round

NAME = "emit_to_wire_ms.sat"
LAYER = "front door and admission"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "program_span"


def reduce(sources):
    return readers_round.emit_to_wire_ms(sources)
