"""Operations and bytes the algorithm needs, computed from shapes."""
