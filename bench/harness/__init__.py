"""The benchmark's own code: data twin, field grower, traffic, reference,
ops/bytes arithmetic, peaks, trace reduction and the serving loops.

Nothing here is imported by the program; later changes to the program
cannot move this yardstick.
"""
