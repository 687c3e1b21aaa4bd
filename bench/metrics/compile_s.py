"""compile_s: the seconds XLA took to compile the traced training step,
as the program measures it (FullRun.compile_s, host clock around
compile())."""


def read(rec):
    return rec.get("program", {}).get("compile_s")
