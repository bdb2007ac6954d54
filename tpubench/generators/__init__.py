"""One traffic generator per file, found by the ``generator`` name in a
traffic file. A generator is a pure function

    generate(params, seed, vocab, *, window_s, warm_s, rate_rps, engine)
        -> schedule

of its arguments: the same seed gives the same schedule. ``schedule``:

    {"loop": "open" | "closed", "clients": int | None,
     "pool": {id: [token ids]},          prompts are lists of pool ids,
     "shapes": [request],                so shared text is stored once
     "background": [request],
     "warm": [request], "main": [request]}
    request = {"id", "parts": [pool id], "max_tokens", "due"?, "warm"?}

``warm`` marks a request whose prompt builds on one an earlier request
of the schedule sent (a document asked again): its first token is timed
apart from that of a prompt nobody has sent before.

``shapes`` run one after another before anything is timed (one request
per program the window will need); ``background`` streams start before
them and run to the end; ``warm`` is the cell's own traffic from a
second stream of the seed, sent before the window so that it opens on a
system in its steady state; ``main`` is what is measured. In an open
loop ``due`` is seconds from the window's start (negative in ``warm``).
"""
