"""Of the host's round (host_round_ms.sat), the share in which the tick thread was on no CPU: its wall less its time.thread_time() over the same phases. The thread wanted the interpreter lock, which the event loop's thread holds while it writes tokens, or a core. Large: the two threads queue for the lock and fewer wake-ups shorten the round; small: the round is the thread's own Python. Same stretch, same gates."""
from benchmarks.harness import readers_round

NAME = "host_round_offcpu_share.sat"
LAYER = "engine scheduler"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "program_span"


def reduce(sources):
    return readers_round.host_round_offcpu_share(sources)
