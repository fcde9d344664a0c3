"""Share of the prompt tokens admitted in the window that the engine
attached from resident prefix pages instead of prefilling: the engine's
``shared_tokens`` counter over the admitted prompts' lengths (%)."""


def read(ctx):
    total = ctx.work["admitted_prompt_tokens"]
    if not total:
        return None
    return 100.0 * ctx.work["shared_tokens"] / total
