"""The plain reference: BLS12-381 in pure Python integers, as far as the
benchmark needs it: fields, curve points, the optimal-ate pairing, hash-to-
G2, point serialisation and the verification of one aggregate. Cut from a
copy of the program's host anchor `grandine_tpu/crypto/` taken at PR 23,
so that what decides `correct` imports nothing of the program and no later
PR can change it; where the anchor takes a shortcut by an endomorphism
(subgroup check, cofactor clearing) this goes by the definition instead:
[r]P = O and the plain h_eff ladder. The generators sign with it, the
reference verifies with it."""
