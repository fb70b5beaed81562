"""Input staging: bytes of one round's sampled batch as the trainer hands
it to ``jax.device_put`` (a count from the batch's shapes)."""


def read(run):
    return run.facts.get("stage_bytes_per_round")
