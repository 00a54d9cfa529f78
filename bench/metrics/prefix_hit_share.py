"""KV pool: prompt tokens served from the prefix trie, the engine's
``prefix_hit_tokens`` counter over the window, as a share of the prompt
tokens of the requests admitted in the window."""


def read(ctx):
    if ctx.prompt_tokens_admitted <= 0:
        return None
    return 100.0 * ctx.prefix_hit_tokens / ctx.prompt_tokens_admitted
