"""Mean time of a token from the tick thread's push (_token_out) to the return of the event loop's writer.write, rate cell: the last leg of every token's way, the first token's included (health()['stream']: emit_to_wire_us over stream_tokens), in the run's two profiled, untraced stretches: before the trace starts and after stop_trace returned."""
from benchmarks.harness import readers_round

NAME = "emit_to_wire_ms.rate"
LAYER = "front door and admission"
UNIT = "ms"
MOVES = "ttft_p50_ms"
SOURCE = "program_span"


def reduce(sources):
    return readers_round.emit_to_wire_ms(sources, tail=True)
