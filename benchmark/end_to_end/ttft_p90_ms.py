"""Due time -> first SSE ``{"token"}`` frame at the client, 90th percentile
over the requests due in the window. A failed, refused or unanswered request
is a miss and ranks above every value.

No cell of ``BENCHMARK.json`` reports it yet. In ``mistral7b-chat-steady`` it
cannot carry a bound: a window holds 57 requests, each waits a uniform 0-8
decode steps for the next chunk boundary, and at 0.8 x the knee one request
in six also waits for one of the program's 16 serving threads, so the 90th
percentile read 605-4492 ms over eight seeds (PERF.md, Findings of PR 22). It
is here for the cell PERF.md's Open questions name for it
(``mistral7b-chat-short``: some hundreds of short requests a window), which
can then arrive as data files and entries alone."""

from measure import ttft_percentile_ms


def read(run):
    return ttft_percentile_ms(run, 90)
