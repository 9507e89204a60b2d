"""Operations and bytes one request needs, by family, from its shapes.

Each module ``work/<problem>.py`` gives ``count(shape, reconstruct) ->
(ops, bytes)``. Operations are the recurrence's min/max-plus operations
(an addition or a comparison each); bytes are what the request carries in
and out: its instance, its answer and, when asked for, its decoded solution.
Neither counts tables the program happens to materialize, so the counts
read the same whatever implements the family.
"""
