"""prefix_hit_share: prompt tokens the prefix cache served
(``prefix_hit_tokens``) over prompt tokens admitted in the window."""


def read(ctx):
    if not ctx.admitted_prompt_tokens:
        return None
    return 100.0 * ctx.hit_tokens / ctx.admitted_prompt_tokens
