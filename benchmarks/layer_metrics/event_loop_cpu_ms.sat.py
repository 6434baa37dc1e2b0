"""CPU of the gateway's event-loop thread a decode tick, whatever it was used for (a wake-up a token, asyncio.wait, json.dumps, the writes): the thread's time.thread_time() stamped at each token write (health()['stream']['event_loop_cpu_us']), its difference over the profiled, untraced stretch before the trace, over the decode ticks there. With the tick thread's CPU it is what the one interpreter lock has to fit into a tick."""
from benchmarks.harness import readers_round

NAME = "event_loop_cpu_ms.sat"
LAYER = "front door and admission"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "program_span"


def reduce(sources):
    return readers_round.event_loop_cpu_ms(sources)
