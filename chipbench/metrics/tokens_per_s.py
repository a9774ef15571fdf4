"""Generated tokens whose block ended inside the window, over its seconds.

Every token counts once per sequence of the batch; a job's first block
also counts the token its prefill chose.
"""


def read(ctx):
    tokens = sum(b.tokens for b in ctx.recorder.blocks if ctx.in_window(b.end))
    return tokens / ctx.seconds
