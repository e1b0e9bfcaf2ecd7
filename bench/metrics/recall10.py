"""recall@10 of the checked answers against the reference's top-10, %."""


def read(run):
    return 100.0 * run.check.recall10 if run.check.checked else None
