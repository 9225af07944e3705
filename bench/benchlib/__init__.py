"""The benchmark's own library: traffic generation, seeded weights, the
plain reference model, trace reduction and the run harness.

Nothing here is imported by the program under test; the harness imports
the program (`src/repro`) only to build and drive its server.
"""
