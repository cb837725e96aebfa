"""The benchmark's own library: manifest discovery, window arithmetic, the
peak table, span and device-trace reduction, seed-made weights and traffic.
Nothing here imports the program under test."""
