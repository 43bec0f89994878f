"""One module per traffic generator, found by the name a mix file gives
(`"generator"`): `check(mix)`, `make_batch(mix, seed, k)`, `fill_steps(mix)`,
`fill_batch(mix, seed, j)`, `filled_rows(mix)`, `examples(mix)`. A generator
is pure numpy: it imports nothing of the program and nothing of jax."""
