"""Seconds of set-up in the program's eager initializer
(``init_model_variables`` and ``create_train_state``, called as
``run_training`` calls them): some two hundred compile requests under a
second each that JAX's persistent cache never keeps, so every process pays
them (ROADMAP S3)."""


def read(run):
    return run.facts.get("init_s")
