"""Long prompts the chunk lane advanced per tick it ran: two where two
stood in it side by side, each by a chunk of its own (a tick's token
steps then carry two chunks where they carried one; the device pays for
each chunk as if it ran alone), one where a prompt stood alone.
prefill_chunks (every chunk dispatched, a prompt's last with them) over
the ticks that dispatched them, prefill_chunks less
chunk_pair_dispatches (the ticks that advanced two), both differenced
over the window; 1.0 where the lane never ran.  None where the program
has no such counter."""


def read(ctx):
    c = ctx["counters"]
    if "chunk_pair_dispatches" not in c or "prefill_chunks" not in c:
        return None
    chunks = c["prefill_chunks"]
    if not chunks:
        return 1.0
    return chunks / (chunks - c["chunk_pair_dispatches"])
