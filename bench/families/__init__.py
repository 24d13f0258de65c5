"""One module per model family, found by the ``family`` a configuration
file names (``bench.model.family``).  A family module gives:

    from_config(cfg)              its sizes record (a ``bench.model.Model``)
    trunk(m, key), head(m, key)   the seeded weight generators, traced
                                  inside ``bench.weights.zoo``'s one call
    program_config(m, program)    the program's config object for ``m``
    token_flops(m, context)       trunk FLOPs of one token that attends
                                  ``context`` positions
    score_work(m, rows, seq)      {kernel: (flops, bytes)} of the trunk of
                                  one scoring call over ``rows`` sequences
    decode_work(m, lengths, page_size)
                                  {kernel: (flops, bytes)} of the trunk of
                                  one decode step whose real rows attend
                                  ``lengths`` positions

Its plain reference is ``bench/references/<family>.py``.
"""
