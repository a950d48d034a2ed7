"""KV cache manager: prompt tokens served from shared prefix pages
(``StepStats.shared_tokens``) over the prompt tokens of the requests
admitted, in percent."""


def read(run):
    prompt = sum(r["prompt_len"] for r in run.requests if r["admitted"] is not None)
    if not prompt:
        return None
    return 100.0 * run.extra["shared_tokens"] / prompt
