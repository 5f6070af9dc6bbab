"""Programs compiled or loaded from the persistent cache during set-up."""


def read(run):
    return float(run.extras["setup_programs"])
