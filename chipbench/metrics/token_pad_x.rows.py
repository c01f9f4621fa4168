"""Tokens the LLM scorer launched (rows after bucket padding times the
padded sequence width) over the real tokens of the rows it scored, over
the window's queries."""
from chipbench import program


def read(run):
    return program.ratio(program.predicate_total(run, "tokens_launched"),
                         program.predicate_total(run, "tokens_real"))
