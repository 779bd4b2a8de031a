"""The program's ``workflow.run`` span (repro.obs): the registered
tokenize -> pack workflow run in memory, in seconds."""


def read(run):
    runs = [s for s in run.get("program_spans") or ()
            if s[0] == "workflow.run"]
    if not runs:
        return None
    return sum(s[2] - s[1] for s in runs) * 1e-9
