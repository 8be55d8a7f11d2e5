"""Share (%) of prompt tokens that the prefix cache held: the cached
tokens (``PrefillTask.cached_tokens``) over the prompt tokens of every
``start_prefill`` the run made, restores of preempted sequences
included."""


def read(rec):
    calls = [c for c in rec.prefills if c[0] >= rec.window[0]]
    prompt = sum(p for _, p, _ in calls)
    return 100.0 * sum(c for _, _, c in calls) / prompt if prompt else None
