"""Column generators, one module a kind, found by the `kind` a
configuration's column names. Each module has

    generate(rng, docs, spec, pools, made) -> (values, codes, domain)

with `domain[codes] == values`: `codes` index the column's domain, which
is what the reference's histogram is built over; `made` holds the
(values, codes, domain) of the columns made before it."""
