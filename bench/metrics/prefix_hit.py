"""Share of the prompt tokens admitted in the window that the engine did
not prefill, because registered prefix pages already held them:
``1 - engine.prefill_tokens / prompt tokens admitted``, both counted over
the steps that ended in the window."""


def read(run):
    admitted = run.counters["prompt_tokens_admitted"]
    if admitted == 0:
        return None
    return 100.0 * (1.0 - run.counters["prefill_tokens"] / admitted)
