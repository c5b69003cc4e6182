"""Operations and bytes each kernel or step needs, computed from shapes.
One file per kernel; each says which of the two roofs bounds it."""
